import os
import sys
import tempfile

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# smoke tests and benches must see the single real device, NOT the 512 fake
# ones the dry-run's main() asks for.
os.environ.pop("XLA_FLAGS", None)


@pytest.fixture()
def fs():
    from repro.lst import LocalFS
    return LocalFS()


@pytest.fixture()
def tmp_table_path():
    return tempfile.mkdtemp() + "/table"


@pytest.fixture()
def sales_columns():
    return {
        "s_id": np.array([1, 2, 3, 4, 5, 6], np.int64),
        "s_type": np.array(["a", "a", "b", "b", "c", "c"]),
        "price": np.array([10.0, 20.0, 30.0, 40.0, 50.0, 60.0]),
    }
