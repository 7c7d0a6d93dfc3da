"""The chip benchmark's harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's limits sits in a file of its own under
``benchmarks/chip/`` and is found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   sizes as run, source, cuts, plain reference
* ``families/<family>.py``    a family's mapping onto the program's model
                              configuration, and its operation counts
* ``traffic/<mix>.json``      parameters read by the generator of its kind
* ``metrics/<metric>.py``     a reader: ``read(readings) -> float | None``
* ``limits/<workload>.json``  the limit of every number ``correct`` compares
* ``reference/<name>.py``     a plain float32 ``jax.numpy`` model

The yardstick lives here too and imports nothing of the program: peaks
(``peaks.json``), operation and byte counts (``flops`` and each family's
counts), the trace reduction (``trace``), the corpus generator
(``corpus``) and the comparison that decides ``correct`` (``check``). From
the program the harness takes only the system under test (a family's
``model_config`` is the bridge to it) and what its spans, counters and
traces say.
"""
