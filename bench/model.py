"""The system under test, built through the program's own path.

``ModelConfig`` from a configuration file's ``program`` group; weights
from ``bench/weights.py``; PMQ through ``repro.core.pipeline.calibrate``
and ``compress_for_serving``; serving through ``PagedServingEngine``.

PMQ is the offline step of a deployment, done before the weights are
loaded. The compressed ``blocks`` are kept under ``bench/.cache/weights``,
keyed by the configuration file, the benchmark's weight rule and every
source file of ``src/repro``; a later run in the same checkout loads them
and remakes only the uncompressed leaves (embeddings, final norm) from the
seed. The first run compresses, and says so.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"

__all__ = ["model_config", "build_params", "build_engine", "cache_key"]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, QuantConfig

    q = conf["pmq"]
    return ModelConfig(
        name=conf["name"],
        quant=QuantConfig(
            enabled=True, target_avg_bits=q["target_avg_bits"],
            bit_choices=tuple(q["bit_choices"]), group=q["group"],
            attn_bits=q["attn_bits"],
        ),
        **conf["program"],
    )


def _check_tree(cfg, tree) -> None:
    """The benchmark's tree has the shapes and types of the program's."""
    from repro.models.registry import get_model

    want = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: tree)
    fw = {jax.tree_util.keystr(p): (l.shape, l.dtype)
          for p, l in jax.tree_util.tree_flatten_with_path(want)[0]}
    fg = {jax.tree_util.keystr(p): (l.shape, l.dtype)
          for p, l in jax.tree_util.tree_flatten_with_path(got)[0]}
    if fw != fg:
        diff = sorted(set(fw.items()) ^ set(fg.items()))
        raise RuntimeError(f"weight tree differs from the program's: {diff}")


def cache_key(conf: dict) -> str:
    """Hash of everything that decides the compressed weights."""
    h = hashlib.sha256()
    h.update(json.dumps(conf, sort_keys=True).encode())
    h.update(jax.__version__.encode())
    for f in [BENCH / "weights.py", BENCH / "model.py",
              *sorted((ROOT / "src" / "repro").rglob("*.py"))]:
        h.update(str(f.relative_to(ROOT)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()[:16]


def _save(blocks, path: Path) -> None:
    leaves, treedef = jax.tree_util.tree_flatten(blocks)
    tmp = path.with_name(path.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    meta, off = [], 0
    with open(tmp / "leaves.bin", "wb") as fh:
        for leaf in leaves:
            a = np.ascontiguousarray(np.asarray(leaf))
            fh.write(a.tobytes())
            meta.append((a.dtype.name, a.shape, off))
            off += a.nbytes
    with open(tmp / "meta.pkl", "wb") as fh:
        pickle.dump({"treedef": treedef, "leaves": meta}, fh)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)


def _load(path: Path):
    with open(path / "meta.pkl", "rb") as fh:
        meta = pickle.load(fh)
    raw = np.memmap(path / "leaves.bin", dtype=np.uint8, mode="r")
    leaves = []
    for name, shape, off in meta["leaves"]:
        dt = jnp.dtype(name)
        n = int(np.prod(shape)) * dt.itemsize
        leaves.append(jax.device_put(
            np.frombuffer(raw[off:off + n], dtype=dt).reshape(shape)))
    return jax.tree_util.tree_unflatten(meta["treedef"], leaves)


def _compress(cfg, conf: dict):
    """PMQ-compressed ``blocks`` of the benchmark's weights (the program's
    calibration and layer-uniform serving compression)."""
    from repro.core import pipeline

    q = conf["pmq"]
    params = weights.make_tree(conf["program"], conf["weights"]["seed"])
    _check_tree(cfg, params)
    rng = np.random.default_rng(q["calib_seed"])
    tokens = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (q["calib_batch"], q["calib_len"])).astype(np.int32))
    calib = pipeline.calibrate(params, tokens, cfg)
    params_c, avg_bits = pipeline.compress_for_serving(
        params, calib, cfg, target_avg_bits=q["target_avg_bits"])
    return params_c["blocks"], avg_bits


def build_params(cfg, conf: dict, log=print):
    """``(params, info)``: the served tree, compressed blocks from the
    cache (compressing first where the cache has none)."""
    path = CACHE / "weights" / f"{conf['name']}-{cache_key(conf)}"
    info = {"cache": str(path), "compressed_now": False}
    if not (path / "meta.pkl").exists():
        t0 = time.perf_counter()
        blocks, avg_bits = _compress(cfg, conf)
        _save(blocks, path)
        info.update(compressed_now=True, compress_s=time.perf_counter() - t0,
                    avg_bits=avg_bits)
        log(f"compressed {conf['name']} to {avg_bits:.3f} average expert "
            f"bits in {info['compress_s']:.1f} s; saved to {info['cache']}")
        del blocks
    t0 = time.perf_counter()
    params = dict(weights.make_tree(conf["program"], conf["weights"]["seed"],
                                    top_only=True))
    params["blocks"] = _load(path)
    jax.block_until_ready(params)
    info["load_s"] = time.perf_counter() - t0
    return params, info


def build_engine(cfg, conf: dict, traffic: dict, params):
    """The paged engine a cell deploys: ``slots`` from the traffic, page
    size from the configuration, a pool that holds every slot's worst case;
    every other setting at the program's default."""
    from repro.serving import EngineConfig, PagedServingEngine

    bs = conf["engine"]["block_size"]
    worst = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    per_slot = -(-worst // bs) + 1
    slots = traffic["slots"]
    return PagedServingEngine(cfg, params, EngineConfig(
        max_slots=slots, block_size=bs, num_blocks=slots * per_slot,
        max_blocks_per_slot=per_slot,
    ))
