"""Each cell's functions at a tiny size on the CPU (kernels interpreted):
the result line's schema in both modes, and ``correct`` turning false
when a served answer is perturbed where it is produced."""
import dataclasses
import json

import numpy as np
import pytest

import harness
import tiny

LIMITS = json.load(open(tiny.os.path.join(
    tiny.BENCH, "configs", "sift1m-f32-250k.json")))["limits"]
CELLS = [w["name"] for w in harness.load_spec(tiny.ROOT)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_line(tiny_root, cell, trace):
    out = tiny.run(tiny_root, cell, trace=trace)
    spec = harness.load_spec(tiny_root)
    tiny.check_line(out, spec, cell, trace)
    assert out["correct"] is True, out["checks"]
    json.dumps(out)


def _patch_search(monkeypatch, alter):
    """Break the served search underneath the harness: ``alter`` gets
    the device result and the queries and returns the result served."""
    from repro.serving import coordinator
    real = coordinator.device_anns

    def broken(ds, queries, p, **kw):
        return alter(real(ds, queries, p, **kw), queries)

    monkeypatch.setattr(coordinator, "device_anns", broken)


def _state_unchanged(r, q):
    # the loop's carried results as it started: nothing found
    return r._replace(ids=r.ids * 0 - 1, dists=r.dists * 0 + np.inf)


def _half_batch(r, q):
    # only the first half of the batch searched; the rest get its rows
    half = r.ids.shape[0] // 2
    idx = np.arange(r.ids.shape[0]) % max(half, 1)
    return r._replace(ids=r.ids[idx], dists=r.dists[idx])


def _answer_altered(r, q):
    # one served id replaced by its neighbour in id order
    return r._replace(ids=r.ids.at[0, 0].set(r.ids[0, 0] + 1))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_turns_correct_false(tiny_root, monkeypatch, cell, fault):
    _patch_search(monkeypatch, fault)
    out = tiny.run(tiny_root, cell)
    assert out["correct"] is False, out["checks"]


def test_control_turns_correct_false(tiny_root):
    import control
    for seed in (3, 2 ** 31 + 5, 77):
        got = control.control_checks(tiny_root, CELLS[0], seed)
        assert got["correct"] is False, got
        # the number the control fails is the distance gap
        assert got["checks"]["dist_gap"] > LIMITS["dist_gap_max"], got
