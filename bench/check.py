"""The comparison that decides ``correct``: the program's first chunk of
rounds against the plain reference (``bench/reference``).

``compare`` reads these numbers; a cell's workload file names those it is
held to, each with its limit (``limits``), and ``verdict`` holds it to them:

  * ``loss_gap``: over the chunk's rounds and both sides (the mean client
    loss on the exit heads, the mean server loss), the largest
    ``|program - reference| / |reference|``;
  * ``moment_gap``: Adam's first moment after the chunk, the optimizer's
    record of every gradient it was given, leaf by leaf over every client
    and server: the largest ``| |m_p| - |m_r| |`` over the larger of
    ``|m_r|`` and the median leaf's ``|m_r|``;
  * ``change_gap``: the same measure on the change of every parameter and
    BatchNorm statistic from the seed's initial value to the end of the
    chunk (after the chunk's last Eq. (1) aggregation);
  * ``fine_change_gap``: over the trainable leaves whose reference change
    is under ``FINE`` of their initial norm (steps finer than the leaf's
    own size, as a BatchNorm scale's 3e-3 steps on 1.0), the largest
    ``| |dp| - |dr| | / |dr|``: where the storage precision of the weights
    shows.

A leaf whose reference moment is under a thousandth of the median leaf's
has a gradient that is nought to rounding; its change is round-off under
Adam, so it is left out of the change numbers (the ResNet cells have none;
the rule is there for configurations that do).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import numpy as np

#: a reference moment under this share of the median leaf's is nought
NOUGHT = 1e-3
#: a change under this share of the leaf's initial norm is a fine one
FINE = 0.05
NUMBERS = ("loss_gap", "moment_gap", "change_gap", "fine_change_gap")


def named(tree, prefix: str) -> Dict[str, np.ndarray]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {prefix + jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
            for path, leaf in leaves}


def layout(clients, client_m, servers, server_m
           ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """(parameters and statistics, first moments), named by position."""
    params, moments = {}, {}
    for i, c in enumerate(clients):
        params.update(named(c, f"client{i}"))
    for j, s in enumerate(servers):
        params.update(named(s, f"server{j}"))
    for i, m in enumerate(client_m):
        moments.update(named(m, f"client{i}.m"))
    for j, m in enumerate(server_m):
        moments.update(named(m, f"server{j}.m"))
    return params, moments


def _norms(leaves: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v.ravel())) for k, v in leaves.items()}


def _gaps(prog: Dict[str, float], ref: Dict[str, float], floor: float
          ) -> Dict[str, float]:
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in ref}


def _moment_key(param_key: str) -> str:
    """``client3['trainable']['out']['w']`` -> ``client3.m['out']['w']``,
    the name of its first moment (statistics have none)."""
    return param_key.replace("['trainable']", ".m", 1)


def compare(program: Dict, reference: Dict, initial: Dict) -> Dict[str, float]:
    """``program`` and ``reference``: ``losses`` [rounds, 2] and the
    ``clients``/``client_m``/``servers``/``server_m`` trees after the chunk.
    ``initial``: the same trees of the reference at round zero."""
    broken = {n: float("inf") for n in NUMBERS}
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        return broken
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}

    keys = ("clients", "client_m", "servers", "server_m")
    pp, pm = layout(*(program[k] for k in keys))
    rp, rm = layout(*(reference[k] for k in keys))
    p0, _ = layout(*(initial[k] for k in keys))
    if set(pp) != set(rp) or set(pm) != set(rm) or set(p0) != set(rp):
        return broken
    if not all(np.all(np.isfinite(v)) for v in (*pp.values(), *pm.values())):
        return broken

    rmn = _norms(rm)
    median = float(np.median(list(rmn.values())))
    out["moment_gap"] = max(_gaps(_norms(pm), rmn, median).values())

    moved = [k for k in rp if rmn.get(_moment_key(k), median) >= NOUGHT
             * median]
    dp = _norms({k: pp[k] - p0[k] for k in moved})
    dr = _norms({k: rp[k] - p0[k] for k in moved})
    out["change_gap"] = max(_gaps(dp, dr,
                                  float(np.median(list(dr.values())))
                                  ).values())
    trainable = [k for k in moved if _moment_key(k) in rmn]
    own = _gaps({k: dp[k] for k in trainable}, {k: dr[k] for k in trainable},
                0.0)
    start = _norms({k: p0[k] for k in trainable})
    fine = {k: own[k] for k in trainable if dr[k] < FINE * start[k]}
    out["fine_change_gap"] = max(fine.values()) if fine else 0.0
    # where the worst fine change sits, for the readings' record
    out["fine_leaf"] = max(fine, key=fine.get) if fine else ""
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(numbers[n]) and numbers[n] <= limits[n]
               for n in limits)
