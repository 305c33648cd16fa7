"""Each cell's functions at a tiny size on the CPU (kernels interpreted):
the result line's schema in both modes, and ``correct`` turning false
when a served answer is perturbed where it is produced. A cell on one
chip runs in this process; a cell on several runs in a process of its
own on as many virtual CPU devices (``multichip.py``), all of its runs
in one such process. Besides the benchmark's cells, the tiny copy
holds ``tiny.SEGMENTS_CELL``: four segments behind the mesh router."""
import json

import pytest

import faults
import harness
import multichip
import tiny

LIMITS = json.load(open(tiny.os.path.join(
    tiny.BENCH, "configs", "sift1m-f32-250k.json")))["limits"]
SPEC_CELLS = harness.load_spec(tiny.ROOT)["workloads"]
CHIPS = {w["name"]: w["chips"] for w in SPEC_CELLS}
CHIPS[tiny.SEGMENTS_CELL] = tiny.SEGMENTS
CELLS = list(CHIPS)
FAULTS = ["state_unchanged", "half_batch", "answer_altered"]
# faults only a cell on several chips can have
MESH_FAULTS = ["state_unchanged_on_rank", "answers_altered_on_rank",
               "exchange_left_out"]
FAULT_CASES = [(c, f) for c in CELLS for f in FAULTS] + [
    (c, f) for c in CELLS if CHIPS[c] > 1 for f in MESH_FAULTS]
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tiny_root):
    tiny.add_segments_cell(tiny_root)
    return tiny_root


@pytest.fixture(scope="module")
def mesh_runs(root):
    """Every run the tests below ask of a cell on several chips, made in
    one process per chip count: (cell, trace, fault) -> result line,
    and (cell, "compare") -> ``multichip.compare``'s reading."""
    keys = [(c, t, None) for c in CELLS if CHIPS[c] > 1
            for t in (False, True)]
    keys += [(c, False, f) for c, f in FAULT_CASES if CHIPS[c] > 1]
    out = {}
    for chips in sorted({CHIPS[c] for c in CELLS if CHIPS[c] > 1}):
        mine = [k for k in keys if CHIPS[k[0]] == chips]
        compares = [c for c in CELLS if CHIPS[c] == chips]
        jobs = [{"cell": c, "seed": SEED, "trace": t, "fault": f}
                for c, t, f in mine]
        jobs += [{"compare": c, "seed": SEED} for c in compares]
        got = multichip.run(root, jobs, chips)
        out.update(zip(mine + [(c, "compare") for c in compares], got))
    return out


def _run(request, root, monkeypatch, cell, trace=False, fault=None):
    if CHIPS[cell] > 1:
        return request.getfixturevalue("mesh_runs")[(cell, trace, fault)]
    if fault is not None:
        faults.plant(fault, monkeypatch.setattr)
    return tiny.run(root, cell, seed=SEED, trace=trace)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_line(request, root, monkeypatch, cell, trace):
    out = _run(request, root, monkeypatch, cell, trace)
    spec = harness.load_spec(root)
    tiny.check_line(out, spec, cell, trace)
    assert out["correct"] is True, out["checks"]
    assert len(out["per_device"]["rounds_per_batch"]) == CHIPS[cell]
    json.dumps(out)


# ids as when the faults were this file's ``_<name>`` functions
@pytest.mark.parametrize("cell,fault", FAULT_CASES,
                         ids=[f"{c}-_{f}" for c, f in FAULT_CASES])
def test_fault_turns_correct_false(request, root, monkeypatch, cell,
                                   fault):
    out = _run(request, root, monkeypatch, cell, fault=fault)
    assert out["correct"] is False, out["checks"]


def test_router_serves_what_its_segments_serve(mesh_runs):
    """The router's answers equal a ``QueryCoordinator``'s over the
    same four segment servers, to the bit; the harness counts one round
    count per rank and each segment's index once."""
    got = mesh_runs[(tiny.SEGMENTS_CELL, "compare")]
    assert got["ids_equal"] and got["dists_equal"], got
    assert len(got["rounds"]) == tiny.SEGMENTS and min(got["rounds"]) > 0
    assert got["index_bytes"] == tiny.SEGMENTS * got["one_segment_bytes"]
    line = mesh_runs[(tiny.SEGMENTS_CELL, False, None)]
    assert line["metrics"]["index_hbm_bytes"]["value"] \
        == got["index_bytes"]


@pytest.mark.parametrize("segments,chips,n,error", [
    (3, 3, 1600, "does not split"),
    (4, 1, 1600, "one per chip"),
    (4, 2, 1600, "one per chip"),
])
def test_segments_split_n_one_per_chip(root, segments, chips, n, error):
    spec = harness.load_spec(root)
    _, cfg, _ = harness.find_cell(spec, root, CELLS[0])
    cfg = dict(cfg, segments=segments, n=n)
    cell = {"name": "c", "chips": chips}
    with pytest.raises(ValueError, match=error):
        harness.segments(cfg, cell)


def test_control_turns_correct_false(root):
    import control
    for seed in (3, 2 ** 31 + 5, 77):
        got = control.control_checks(root, CELLS[0], seed)
        assert got["correct"] is False, got
        # the number the control fails is the distance gap
        assert got["checks"]["dist_gap"] > LIMITS["dist_gap_max"], got
