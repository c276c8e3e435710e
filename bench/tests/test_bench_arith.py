"""The benchmark's arithmetic against hand counts, and its copied
generator against the program's own, bit for bit before the relabelling."""
import numpy as np
import pytest
from bench_testkit import REPO  # noqa: F401  (puts the repo on the path)

from bench import generators, stats, tracing


def test_mteps_hand_count():
    # 2^24 input edges in 4 s
    assert stats.mteps(16_777_216, 4.0) == pytest.approx(4.194304)


def test_pull_pass_bytes_hand_count():
    # scale 20: 2^25 directed slots of 4 B, 2^20 labels read and written
    assert stats.pull_pass_bytes(1 << 25, 1 << 20) == 134_217_728 + 8_388_608
    assert stats.least_seconds(819e9, 819e9) == 1.0


def test_reduce_events_hand_count():
    ev = {
        # a loop op holding two ops, a third op, and one past the window
        "devices": {"/device:TPU:0": [["while.1", 0, 160], ["kern", 0, 100],
                                      ["fusion", 100, 50], ["kern", 300, 100],
                                      ["late", 900, 50]]},
        "host": [["bench.window", 0, 500], ["bench.run", 0, 250],
                 ["bench.prepare_labels", 200, 100]],
    }
    s = tracing.reduce_events(ev)
    assert s["window_s"] == pytest.approx(500e-9)
    assert s["busy_s"] == pytest.approx(260e-9)  # [0, 160) and [300, 400)
    assert s["op_s"] == pytest.approx({"while.1": 10e-9, "kern": 200e-9, "fusion": 50e-9})
    assert sorted(s["gaps"]) == pytest.approx(
        sorted([("prepare_labels", 140e-9), ("outside spans", 100e-9)]))
    b = tracing.breakdown(s)
    assert b["device_ops"][0] == ["kern", pytest.approx(200e-9)]


def test_roofline_reader_hand_count():
    import types

    from bench import harness

    reader = harness.Bench().reader("pull_roofline")
    g = types.SimpleNamespace(src=np.zeros(1 << 10), num_vertices=1 << 6)
    ctx = types.SimpleNamespace(
        trace={"op_s": {"gather_reduce_cores_pallas.9": 2e-6, "gather_reduce_cores_pallas.10": 2e-6, "fusion.3": 1.0}, "busy_s": 1.0},
        window={"runs": [{"iterations": 3}, {"iterations": 5}]},
        graph=g, peak={"hbm_bytes_per_s": 1e9})
    least = 8 * (1024 * 4 + 64 * 8) / 1e9  # 8 passes
    assert reader(ctx) == pytest.approx(100 * least / 4e-6)
    assert harness.Bench().reader("pull_kernel_pct")(ctx) == pytest.approx(4e-4)


@pytest.mark.parametrize("symmetric,weighted,chunk", [(True, True, 1 << 11), (False, False, 1 << 18)])
def test_rmat_copy_matches_program(symmetric, weighted, chunk):
    from repro.data.rmat import materialize, rmat_chunks

    mine = generators.RMATStream(scale=10, seed=2**33 + 1, symmetric=symmetric,
                                 weighted=weighted, chunk_edges=chunk)
    theirs = rmat_chunks(10, seed=2**33 + 1, symmetric=symmetric, weighted=weighted,
                         chunk_edges=chunk)
    perm = mine.vertex_perm
    for a, b in zip(mine(), theirs()):
        assert len(a) == len(b)
        np.testing.assert_array_equal(a[0], perm[b[0]])
        np.testing.assert_array_equal(a[1], perm[b[1]])
        for x, y in zip(a[2:], b[2:]):
            np.testing.assert_array_equal(x, y)
    src, dst, w = generators.materialize(mine)
    g = materialize(theirs)
    np.testing.assert_array_equal(src, perm[g.src])
    np.testing.assert_array_equal(dst, perm[g.dst])
    assert (w is None) == (g.weights is None)
    if w is not None:
        np.testing.assert_array_equal(w, g.weights)


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 1])
def test_relabelling_is_a_seeded_permutation(seed):
    a = generators.RMATStream(scale=12, seed=seed)
    b = generators.RMATStream(scale=12, seed=seed)
    np.testing.assert_array_equal(np.sort(a.vertex_perm), np.arange(1 << 12))
    np.testing.assert_array_equal(a.vertex_perm, b.vertex_perm)
    other = generators.RMATStream(scale=12, seed=seed + 1).vertex_perm
    assert (a.vertex_perm != other).mean() > 0.99


@pytest.mark.parametrize("seed", [0, 3])
def test_relabelling_hides_degree_in_ids(seed):
    """Without the relabelling the Kronecker bits make degree fall with the
    number of set bits in an ID; with it, the hubs sit anywhere."""
    s = generators.RMATStream(scale=14, seed=seed, symmetric=True)
    src, _, _ = generators.materialize(s)
    deg = np.bincount(src, minlength=s.num_vertices)
    bits = np.array([bin(v).count("1") for v in range(s.num_vertices)])
    raw = deg[s.vertex_perm]  # degree by Kronecker ID
    assert np.corrcoef(bits, raw)[0, 1] < -0.3
    assert abs(np.corrcoef(bits, deg)[0, 1]) < 0.05
    assert int(np.argmax(raw)) == 0 and int(np.argmax(deg)) != 0
