"""The partition cache: its key follows the program's code, and a hit is the
build itself."""
import dataclasses
import json

import numpy as np
from bench_testkit import REPO, small_bench

from bench import graphs


def test_key_changes_with_program_code(tmp_path):
    src = tmp_path / "repro"
    (src / "core").mkdir(parents=True)
    (src / "core" / "engine.py").write_text("x = 1\n")
    cfg = REPO / "bench" / "configs" / "graph500-s20.json"
    k0 = graphs.cache_key(cfg, src)
    assert graphs.cache_key(cfg, src) == k0
    (src / "core" / "engine.py").write_text("x = 2\n")
    k1 = graphs.cache_key(cfg, src)
    (src / "core" / "new.py").write_text("")
    k2 = graphs.cache_key(cfg, src)
    (src / "notes.txt").write_text("not code")
    assert len({k0, k1, k2}) == 3 and graphs.cache_key(cfg, src) == k2
    assert graphs.cache_key(cfg) != graphs.cache_key(cfg, src)


def test_hit_equals_fresh_build(tmp_path):
    bench = small_bench(tmp_path, scale=10)
    f = bench.config_file("graph500-s20")
    cfg = json.loads(f.read_text())
    cache = tmp_path / "cache"
    first = graphs.load_or_build(f, cfg, cache_dir=cache)
    hit = graphs.load_or_build(f, cfg, cache_dir=cache)
    assert not first.cache_hit and hit.cache_hit
    assert hit.build_s == first.build_s > 0
    pg, src, dst, w, comp, comp_edges, _ = graphs.build(cfg)
    for fld in dataclasses.fields(pg):
        a, b = getattr(pg, fld.name), getattr(hit.pg, fld.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        else:
            assert a == b, fld.name
    for a, b in ((src, hit.src), (dst, hit.dst), (w, hit.weights), (comp, hit.comp),
                 (comp_edges, hit.comp_edges)):
        np.testing.assert_array_equal(a, b)
    # every input tuple counted once, in its component
    assert hit.input_edges == (1 << 10) * cfg["edge_factor"]
    # a changed configuration is another entry; an older key of it goes
    cfg2 = dict(cfg, graph_seed=5)
    f.write_text(json.dumps(cfg2))
    third = graphs.load_or_build(f, cfg2, cache_dir=cache)
    assert not third.cache_hit
    assert len([p for p in cache.iterdir() if p.name.startswith(cfg["name"] + "-")]) == 1
