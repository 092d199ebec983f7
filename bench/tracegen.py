"""Memory traces of workflow tasks, made from a configuration and a seed.

Each task family is a sequence of phases whose durations and memory
levels scale with the task's aggregated input size (KS+ paper, section
II-B).  A phase holds its level or ramps linearly from the previous one;
timing noise grows with the phase's nominal duration and memory carries a
per-execution factor and per-sample jitter.  The parameters live in the
configuration file (``families``).  The same (seed, family, stream) always
gives the same executions.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, NamedTuple

import numpy as np

__all__ = ["Execution", "executions", "history", "node_capacities",
           "order", "split"]

STREAMS = {"history": 0, "cohort": 1, "split": 2, "order": 3}


class Execution(NamedTuple):
    family: str
    input_gb: float
    dt: float
    mem: np.ndarray   # GB per sample, float64


def _rng(seed: int, family: str, stream: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed), zlib.crc32(family.encode()), STREAMS[stream]]))


def _trace(fam: dict, input_gb: float, rng: np.random.Generator,
           dt: float) -> np.ndarray:
    mem_factor = float(np.exp(rng.normal(0.0, fam["mem_sigma"])))
    parts: List[np.ndarray] = []
    prev = 0.05
    for dur_base, dur_per_gb, mem_base, mem_per_gb, ramp in fam["phases"]:
        dur = dur_base + dur_per_gb * input_gb
        rel = fam["timing_sigma"] + fam["timing_growth"] * np.sqrt(max(dur, 0.0))
        dur *= float(np.exp(rng.normal(0.0, rel)))
        n = max(int(round(dur / dt)), 1)
        level = (mem_base + mem_per_gb * input_gb) * mem_factor
        if ramp == "linear":
            parts.append(np.linspace(prev, level, n, endpoint=True))
        else:
            parts.append(np.full(n, level))
        prev = level
    mem = np.concatenate(parts)
    mem = mem * (1.0 + rng.normal(0.0, 0.004, mem.shape))
    return np.maximum(mem, 0.01)


def executions(cfg: dict, seed: int, stream: str,
               n: int) -> Dict[str, List[Execution]]:
    """``n`` executions of every family of ``cfg`` from one stream."""
    dt = float(cfg["dt_s"])
    out: Dict[str, List[Execution]] = {}
    for name, fam in cfg["families"].items():
        rng = _rng(seed, name, stream)
        rows = []
        for _ in range(n):
            x = float(fam["input_median_gb"]
                      * np.exp(rng.normal(0.0, fam["input_sigma"])))
            rows.append(Execution(name, x, dt, _trace(fam, x, rng, dt)))
        out[name] = rows
    return out


def split(cfg: dict, seed: int):
    """Each family's monitoring history, split into training and test
    executions: ``(train, test)`` dicts of lists."""
    full = executions(cfg, seed, "history", int(cfg["history_per_family"]))
    train, test = {}, {}
    for name, rows in full.items():
        perm = _rng(seed, name, "split").permutation(len(rows))
        n_train = max(int(round(cfg["train_frac"] * len(rows))), 2)
        chosen = set(perm[:n_train].tolist())
        train[name] = [r for i, r in enumerate(rows) if i in chosen]
        test[name] = [r for i, r in enumerate(rows) if i not in chosen]
    return train, test


def history(cfg: dict, seed: int) -> Dict[str, List[Execution]]:
    """The training share of each family's monitoring history."""
    return split(cfg, seed)[0]


def order(seed: int, family: str, n: int) -> np.ndarray:
    """A permutation of ``n`` executions of ``family``, from the seed."""
    return _rng(seed, family, "order").permutation(n)


def node_capacities(cfg: dict) -> np.ndarray:
    """The cluster's nodes, each with the configuration's node memory."""
    c = cfg["cluster"]
    return np.full(int(c["nodes"]), float(c["node_memory_gb"]))
