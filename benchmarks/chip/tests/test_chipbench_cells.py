"""Each cell's run end to end at smoke size on the CPU, and with its timed
path broken underneath: ``correct`` must come out false for every fault the
cell can have. The limits are the cells' ``smoke`` limits, looser than
their own. Also the control: the plain reference one precision down, put
in the program's place, comes out not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import smoke
from chipbench import check, train
from chipbench.cell import load_benchmark

TRAIN = "stablelm3b.train_lake"
WORKLOADS = [w["name"] for w in load_benchmark()["workloads"]]


def judged(cell, out):
    ok, checks = check.judge(out.numbers, cell.limits)
    return ok and out.failed == 0, checks


def _broken_build(monkeypatch, module, fault):
    real = module.build

    def build(*args, **kwargs):
        built = real(*args, **kwargs)
        fault(*built)
        return built
    monkeypatch.setattr(module, "build", build)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_is_correct_at_smoke_size(workload):
    cell = smoke.smoke_cell(workload)
    out = smoke.run_smoke(cell, seed=2**40 + 11)
    ok, checks = judged(cell, out)
    assert ok, checks
    assert out.attempted > 0 and out.failed == 0
    assert "setup_s" in out.end_to_end
    assert all(v > 0 for v in out.end_to_end.values())


def _state_unchanged(trainer, *_):
    step = trainer.step_fn

    def unchanged(params, opt, batch):
        kept = jax.tree.map(jnp.copy, (params, opt))   # the step donates
        _, _, metrics = step(params, opt, batch)
        return (*kept, metrics)
    trainer.step_fn = unchanged


def _half_batch(trainer, *_):
    step = trainer.step_fn

    def half(params, opt, batch):
        n = batch["inputs"].shape[0] // 2
        return step(params, opt, {k: v[:n] for k, v in batch.items()})
    trainer.step_fn = half


def _token_altered(trainer, proxy, *_):
    fetch = proxy.loader.next_batch

    def altered():
        b = fetch()
        b["inputs"][0, 3] = (b["inputs"][0, 3] + 1) % 256
        return b
    proxy.loader.next_batch = altered


def _in_window(move: int):
    """The loader's cursor moved by ``move`` rows before the window's second
    batch: a row skipped (+1) or handed out twice (-1), after the steps
    that the reference checks."""
    def fault(trainer, proxy, *_):
        fetch, calls = proxy.loader.next_batch, []

        def moved():
            calls.append(1)
            if len(calls) == train.FIRST_STEPS + 2:
                proxy.loader.row += move
            return fetch()
        proxy.loader.next_batch = moved
    fault.__name__ = {1: "_row_skipped_in_window",
                      -1: "_row_repeated_in_window"}[move]
    return fault


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _in_window(1),
                                   _in_window(-1)],
                         ids=lambda f: f.__name__)
def test_train_cell_with_a_fault_is_not_correct(monkeypatch, fault):
    _broken_build(monkeypatch, train, fault)
    cell = smoke.smoke_cell(TRAIN)
    ok, checks = judged(cell, smoke.run_smoke(cell, seed=5))
    assert not ok, checks
    if fault.__name__.endswith("_in_window"):   # only the row check sees it
        assert checks["rows_wrong"]["value"] > 0, checks


def test_control_is_not_correct():
    """At smoke size, judged by ``check.judge`` against the smoke limits:
    the program comes out correct, the fp8 control and the half-batch
    fault, each put in its place, do not."""
    import calibrate
    got = {g["reading"]: g for g in calibrate.train_seed(
        smoke.smoke_cell(TRAIN), 3, control=True)}
    assert got["program"]["correct"], got["program"]
    assert not got["control_fp8"]["correct"], got["control_fp8"]
    assert not got["fault_half_batch"]["correct"], got["fault_half_batch"]


def test_reference_matches_the_program_forward_at_smoke_size():
    """The plain reference and the program's teacher-forced forward agree
    on the same weights to bf16 rounding."""
    from chipbench import weights
    from chipbench.cell import load_family, load_reference
    from repro.models.model import Model
    cell = smoke.smoke_cell(TRAIN)
    model = Model(load_family(cell.config["family"]).model_config(
        cell.config))
    w = weights.make_params(model.param_template(), 4)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 255)
    prog, _ = model.forward(w, toks)
    ref = load_reference("attn_lm").logits(w, toks, cell.config)
    err = float(jnp.max(jnp.abs(prog - ref)))
    assert err < 0.05 * float(jnp.max(jnp.abs(ref))), err
