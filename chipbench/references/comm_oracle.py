"""Plain NumPy answers of the Communicator's operations.

``x`` is (n, m): row r is rank r's payload.  Each function returns what
every rank must hold afterwards, row r for rank r, following pPython's
semantics (and MPI's for alltoall): nothing of the system under test is
imported.  ``dtype`` lets the control compute the same answers in a
lower precision.
"""
from __future__ import annotations

import numpy as np


def _blocks(v: np.ndarray, n: int) -> np.ndarray:
    """A payload zero-padded to n equal blocks, (n, blk)."""
    blk = -(-v.shape[-1] // n)
    out = np.zeros(v.shape[:-1] + (n * blk,), v.dtype)
    out[..., :v.shape[-1]] = v
    return out.reshape(v.shape[:-1] + (n, blk))


def send(x, src, dst, root):
    out = x.copy()
    out[dst] = x[src]
    return out


def bcast(x, src, dst, root):
    return np.broadcast_to(x[root], x.shape).copy()


def agg(x, src, dst, root):
    n, m = x.shape
    out = np.zeros((n, n * m), x.dtype)
    out[root] = x.reshape(-1)
    return out


def scatter(x, src, dst, root):
    return _blocks(x[root], x.shape[0])


def allreduce(x, src, dst, root):
    total = x[0].copy()
    for r in range(1, x.shape[0]):
        total = total + x[r]
    return np.broadcast_to(total, x.shape).copy()


def alltoall(x, src, dst, root):
    n = x.shape[0]
    b = _blocks(x, n)                               # (n src, n dst, blk)
    return b.transpose(1, 0, 2).reshape(n, -1)


OPS = {"send": send, "bcast": bcast, "agg": agg, "scatter": scatter,
       "allreduce": allreduce, "alltoall": alltoall}


def answer(op: str, x: np.ndarray, *, src: int, dst: int, root: int,
           dtype=None) -> np.ndarray:
    if dtype is not None:
        x = x.astype(dtype)
    return OPS[op](x, src, dst, root)
