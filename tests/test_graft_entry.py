"""Keep the driver entry points working: dryrun_multichip must
compile+run the sharded training paths on the virtual CPU mesh, and
dryrun_elastic must find the same losses across a shrink and a grow
of the world as an uninterrupted run does."""
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def test_dryrun_multichip_8():
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_dryrun_elastic_8():
    import __graft_entry__ as g
    g.dryrun_elastic(8)


def test_entry_signature():
    import __graft_entry__ as g
    assert callable(g.entry)
    assert callable(g.dryrun_multichip)
