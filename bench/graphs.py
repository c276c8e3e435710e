"""The benchmark's graphs: generated from the configuration, partitioned by
the program's own ``partition_2d_streaming``, and cached in the checkout.

The host build takes minutes at Graph500 scale, far more than one run may
spend on set-up, so the first run of a configuration in a checkout builds
and stores the result under ``bench/.cache/graphs/`` and later runs load it.
The cache key hashes the configuration file, this file, the generators, the
references (they count each component's edges) and every ``*.py`` under
``src/repro/``: a change to any program code rebuilds,
so a hit is always the layout the code under test would build.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import re
import shutil
import time
from pathlib import Path

import numpy as np

from bench import generators, reference

BENCH_DIR = Path(__file__).resolve().parent
SRC_DIR = BENCH_DIR.parent / "src" / "repro"
CACHE_DIR = BENCH_DIR / ".cache" / "graphs"
LAYOUT_KEYS = ("p", "l", "lane", "tile_vb", "tile_eb", "build_push")


def cache_key(config_file: Path, src_dir: Path = SRC_DIR) -> str:
    """Hash of what decides the cached graph and layout (hex)."""
    h = hashlib.sha256()
    for f in (config_file, BENCH_DIR / "generators.py", BENCH_DIR / "reference.py",
              Path(__file__)):
        h.update(Path(f).read_bytes())
    for f in sorted(Path(src_dir).rglob("*.py")):
        h.update(str(f.relative_to(src_dir)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()


def rmat_stream(cfg: dict) -> generators.RMATStream:
    return generators.RMATStream(
        scale=cfg["scale"], edge_factor=cfg["edge_factor"], a=cfg["a"],
        b=cfg["b"], c=cfg["c"], seed=cfg["graph_seed"],
        symmetric=cfg["symmetric"], weighted=cfg["weighted"],
    )


@dataclasses.dataclass
class Graph:
    """One configuration's graph as the benchmark holds it."""

    num_vertices: int
    src: np.ndarray  # (E,) uint32 directed slots, both directions
    dst: np.ndarray
    weights: np.ndarray | None
    pg: object  # the program's PartitionedGraph
    comp: np.ndarray  # (V,) int32 weakly connected component of each vertex
    comp_edges: np.ndarray  # (C,) int64 input edge tuples per component
    build_s: float  # wall time of the partition build that filled the cache
    cache_hit: bool

    @property
    def input_edges(self) -> int:
        return int(self.comp_edges.sum())

    def coo(self):
        from repro.core.graph import COOGraph

        return COOGraph(src=self.src, dst=self.dst,
                        num_vertices=self.num_vertices, weights=self.weights)


def _save(path: Path, pg, src, dst, w, comp, comp_edges, meta: dict):
    tmp = path.with_name(path.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    arrays = {"edge.src": src, "edge.dst": dst, "edge.w": w,
              "comp": comp, "comp_edges": comp_edges}
    fields = {}
    for f in dataclasses.fields(pg):
        v = getattr(pg, f.name)
        if isinstance(v, np.ndarray):
            arrays["pg." + f.name] = v
        elif f.name == "config":
            fields[f.name] = dataclasses.asdict(v)
        else:
            fields[f.name] = v
    for name, a in arrays.items():
        if a is not None:
            np.save(tmp / f"{name}.npy", np.ascontiguousarray(a))
    meta = dict(meta, pg_fields=fields,
                arrays=sorted(k for k, a in arrays.items() if a is not None))
    (tmp / "meta.json").write_text(json.dumps(meta, indent=1, default=int))
    stale = re.compile(re.escape(meta["name"]) + r"-[0-9a-f]{20}")
    for old in path.parent.iterdir():
        if stale.fullmatch(old.name):  # this configuration under older keys
            shutil.rmtree(old, ignore_errors=True)
    tmp.rename(path)


def _load(path: Path) -> Graph:
    from repro.core.partition import PartitionConfig, PartitionedGraph

    meta = json.loads((path / "meta.json").read_text())
    arr = {k: np.load(path / f"{k}.npy", mmap_mode="r") for k in meta["arrays"]}
    kw = dict(meta["pg_fields"])
    kw["config"] = PartitionConfig(**kw["config"])
    for f in dataclasses.fields(PartitionedGraph):
        if f.name not in kw:
            kw[f.name] = arr.get("pg." + f.name)
    return Graph(
        num_vertices=meta["num_vertices"], src=arr["edge.src"],
        dst=arr["edge.dst"], weights=arr.get("edge.w"),
        pg=PartitionedGraph(**kw), comp=arr["comp"],
        comp_edges=arr["comp_edges"], build_s=meta["build_s"], cache_hit=True,
    )


def build(cfg: dict):
    """Generate and partition a configuration's graph (no cache)."""
    from repro.core.partition import PartitionConfig, partition_2d_streaming

    stream = rmat_stream(cfg)
    layout = PartitionConfig(**{k: cfg["layout"][k] for k in LAYOUT_KEYS})
    t0 = time.perf_counter()
    pg = partition_2d_streaming(stream, stream.num_vertices, layout)
    build_s = time.perf_counter() - t0
    src, dst, w = generators.materialize(stream)
    comp = reference.component_labels(src, dst, stream.num_vertices)
    # every input tuple is stored both ways, both in its component
    comp_edges = np.bincount(comp[src], minlength=int(comp.max()) + 1) // 2
    return pg, src, dst, w, comp, comp_edges.astype(np.int64), build_s


def load_or_build(config_file: Path, cfg: dict, cache_dir: Path = CACHE_DIR,
                  src_dir: Path = SRC_DIR) -> Graph:
    """The configuration's graph from the checkout's cache, built on a miss."""
    path = Path(cache_dir) / f"{cfg['name']}-{cache_key(config_file, src_dir)[:20]}"
    if (path / "meta.json").exists():
        return _load(path)
    pg, src, dst, w, comp, comp_edges, build_s = build(cfg)
    _save(path, pg, src, dst, w, comp, comp_edges,
          {"name": cfg["name"], "num_vertices": int(pg.num_vertices),
           "build_s": build_s})
    g = _load(path)
    g.cache_hit = False
    return g
