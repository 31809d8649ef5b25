"""``__graft_entry__.entry()`` is the repo's declared compile check: it
must hand back the step a deployment runs, and that step must leave the
tables the host planner resolved."""

import importlib.util
from pathlib import Path

import jax
import numpy as np

spec = importlib.util.spec_from_file_location(
    "__graft_entry__", Path(__file__).resolve().parent.parent / "__graft_entry__.py"
)
graft = importlib.util.module_from_spec(spec)
spec.loader.exec_module(graft)


def test_entry_is_the_production_step_and_matches_the_mirror():
    fn, args = graft.entry()
    right, deleted, starts = jax.jit(fn)(*args)
    _key, _dyn, lanes, mirror = graft._example_batch(n_docs=4)
    # deterministic: the lanes entry() closed over are these
    assert (np.asarray(args[1]) == lanes).all()
    n, n_segs = mirror.n_rows, mirror.n_segs
    assert n and mirror._host_deleted_rows
    host_deleted = np.zeros(n, bool)
    host_deleted[sorted(mirror._host_deleted_rows)] = True
    for doc in range(4):
        assert (np.asarray(right)[doc, :n] == np.asarray(mirror.list_next)[:n]).all()
        assert (np.asarray(deleted)[doc, :n] == host_deleted).all()
        assert (
            np.asarray(starts)[doc, :n_segs] == np.asarray(mirror.head_of_seg)
        ).all()
