"""The built index, cached per seed inside the checkout.

A build is most of what a cold run costs, and every run is a new process.
So the arrays a backend builds are kept under ``bench/cache/index/<key>/``
(ignored by git).  The key hashes the configuration file, the seed and the
contents of every source file of the program (``src/repro/**``), so a change
to the program, to the deployment or to the seed builds anew; runs 2..n of a
seed only load.
"""
from __future__ import annotations

import hashlib
import os
import pathlib

import numpy as np

from bench.spec import BENCH_DIR, ROOT

CACHE_DIR = BENCH_DIR / "cache"
INDEX_DIR = CACHE_DIR / "index"


def program_files(src: pathlib.Path = ROOT / "src" / "repro") -> list:
    return sorted(p for p in src.rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts
                  and p.suffix != ".pyc")


def cache_key(config_file: pathlib.Path, seed: int,
              src: pathlib.Path = ROOT / "src" / "repro") -> str:
    h = hashlib.sha256()
    h.update(pathlib.Path(config_file).read_bytes())
    h.update(b"\0seed=%d\0" % int(seed))
    for p in program_files(src):
        h.update(str(p.relative_to(src)).encode() + b"\0")
        h.update(p.read_bytes())
    return h.hexdigest()[:24]


def entry_dir(key: str) -> pathlib.Path:
    return INDEX_DIR / key


def load(key: str) -> dict | None:
    """The cached arrays as numpy, or None when the key has no entry."""
    path = entry_dir(key) / "index.npz"
    if not path.is_file():
        return None
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def save(key: str, arrays: dict) -> None:
    """Write atomically: a run killed mid-write leaves no entry behind."""
    d = entry_dir(key)
    d.mkdir(parents=True, exist_ok=True)
    tmp = d / f"index.tmp{os.getpid()}.npz"
    np.savez(tmp, **{k: np.asarray(v) for k, v in arrays.items()})
    os.replace(tmp, d / "index.npz")
