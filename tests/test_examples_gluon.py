"""The first third of the --quick example gates (tests/_examples.py)."""
import pytest

from _examples import QUICK, run_quick


@pytest.mark.parametrize("name", QUICK[:7])
def test_example_quick(name):
    run_quick(name)
