"""Continuous-batching scheduler: tick planning + admission agreement.

The scheduler owns host-side slot bookkeeping and turns it into *ticks*
— single jitted dispatches over the whole slot batch in which each row
independently carries a prefill chunk, one decode token, or nothing
(idle rows are masked out by ``lengths == 0``).  Two tick policies:

* ``conservative`` (default) — prefill chunks and decode tokens never
  share a dispatch: chunk ticks run at a fixed width
  ``cfg.prefill_chunk`` while decode rows idle; decode ticks are always
  width 1.  Every slot therefore sees exactly the same per-token
  computation it would see alone in the batch, which keeps greedy
  outputs bit-identical between solo and batched serving.
* ``mixed`` — decode rows join chunk ticks as single-token rows (their
  token is spliced from the device-resident next-token buffer inside
  the dispatch).  Fewer dispatches under mixed prefill/decode load, at
  the cost of ULP-level divergence (decode runs in chunk-mode attention
  with a different dispatch width).

Counters per slot (``SlotState``): ``fed`` tokens written to the KV
cache so far, ``sampled`` generated tokens whose sampling has been
*dispatched*, ``recorded`` generated tokens the host has actually seen.
With the engine's one-tick-deep pipeline, ``sampled`` runs ahead of
``recorded``; planning uses ``sampled`` (host-predictable), completion
uses ``recorded``.  ``epoch`` guards slot reuse: a tick's sample rows
remember the epoch they were planned against, and finish-processing
drops rows whose slot has since been released (e.g. the speculative
token dispatched in the tick after an EOS).

Cross-host admission goes through :func:`agree_admission_count`: each
rank proposes how many queued requests it can admit and a Communicator
agg+bcast round takes the fleet-wide minimum, so slot assignment stays
identical on every rank without ad-hoc host blocking.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.comms import Communicator


@dataclasses.dataclass
class SlotState:
    rid: int
    prompt: np.ndarray
    cap: int                       # generated-token budget (>= 1)
    temperature: float
    eos_id: Optional[int]
    epoch: int
    fed: int = 0                   # tokens written into the cache
    sampled: int = 0               # generated tokens dispatched
    recorded: int = 0              # generated tokens seen by the host
    done: bool = False             # no further ticks (EOS or cap)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def prefilling(self) -> bool:
        return self.fed < self.prompt_len

    @property
    def decode_ready(self) -> bool:
        return (not self.done and not self.prefilling
                and self.sampled < self.cap)


@dataclasses.dataclass
class TickPlan:
    """One dispatch: (B, width) token rows + which rows sample."""

    kind: str                       # "chunk" | "decode"
    width: int
    tokens: np.ndarray              # (B, width) int32 host tokens
    use_next: np.ndarray            # (B,) bool: row 0 token comes from the
                                    # device next-token buffer instead
    starts: np.ndarray              # (B,) int32
    lengths: np.ndarray             # (B,) int32 (0 = idle row)
    samples: List[Tuple[int, int, int]]  # (slot, epoch, gen_index)
    tick: int = -1                  # the engine's id for it, once dispatched


class Scheduler:
    def __init__(self, slots: int, chunk: int, policy: str = "conservative"):
        if policy not in ("conservative", "mixed"):
            raise ValueError(f"unknown tick policy {policy!r}")
        self.n_slots = slots
        self.chunk = max(int(chunk), 1)
        self.policy = policy
        self.states: List[Optional[SlotState]] = [None] * slots
        self._epoch = 0

    # ---------------------------------------------------------------- slots
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.states) if s is None]

    def active(self) -> List[Tuple[int, SlotState]]:
        return [(i, s) for i, s in enumerate(self.states) if s is not None]

    def assign(self, slot: int, rid: int, prompt: np.ndarray, cap: int,
               temperature: float, eos_id: Optional[int]) -> SlotState:
        assert self.states[slot] is None
        self._epoch += 1
        st = SlotState(rid=rid, prompt=prompt.astype(np.int32), cap=cap,
                       temperature=temperature, eos_id=eos_id,
                       epoch=self._epoch)
        self.states[slot] = st
        return st

    def release(self, slot: int) -> None:
        self.states[slot] = None

    def has_work(self) -> bool:
        return any(s is not None and (s.prefilling or s.decode_ready)
                   for s in self.states)

    # ---------------------------------------------------------------- ticks
    def plan(self) -> Optional[TickPlan]:
        """Plan the next tick, advancing ``fed``/``sampled`` counters as
        if it were already dispatched (the engine dispatches it next)."""
        B = self.n_slots
        prefill = [(i, s) for i, s in self.active() if s.prefilling]
        decode = [(i, s) for i, s in self.active() if s.decode_ready]
        if not prefill and not decode:
            return None

        if prefill:
            C = self.chunk
            tokens = np.zeros((B, C), np.int32)
            starts = np.zeros((B,), np.int32)
            lengths = np.zeros((B,), np.int32)
            use_next = np.zeros((B,), bool)
            samples: List[Tuple[int, int, int]] = []
            for i, s in prefill:
                n = min(C, s.prompt_len - s.fed)
                tokens[i, :n] = s.prompt[s.fed:s.fed + n]
                starts[i] = s.fed
                lengths[i] = n
                s.fed += n
                if not s.prefilling:        # this chunk samples token 0
                    samples.append((i, s.epoch, 0))
                    s.sampled = 1
            if self.policy == "mixed":
                for i, s in decode:
                    starts[i] = s.fed
                    lengths[i] = 1
                    use_next[i] = True
                    samples.append((i, s.epoch, s.sampled))
                    s.fed += 1
                    s.sampled += 1
            return TickPlan("chunk", C, tokens, use_next, starts, lengths,
                            samples)

        tokens = np.zeros((B, 1), np.int32)
        starts = np.zeros((B,), np.int32)
        lengths = np.zeros((B,), np.int32)
        use_next = np.zeros((B,), bool)
        samples = []
        for i, s in decode:
            starts[i] = s.fed
            lengths[i] = 1
            use_next[i] = True
            samples.append((i, s.epoch, s.sampled))
            s.fed += 1
            s.sampled += 1
        return TickPlan("decode", 1, tokens, use_next, starts, lengths,
                        samples)


def agree_admission_count(comm: Communicator, n: int) -> int:
    """Fleet-wide admission agreement: every rank proposes how many
    queued requests it can admit this round; the agreed count is the
    minimum over ranks, computed on rank 0 (pPython's leader-on-rank-0
    agg convention) and broadcast back.  With identical SPMD host state
    this is the identity; it exists so a rank under local pressure
    (e.g. pool exhaustion) holds the whole fleet back coherently."""
    import jax.numpy as jnp

    if comm.size == 1:
        return n

    def body(x):
        allc = comm.agg(x, root=0)          # (size,) on root, 0 elsewhere
        return comm.bcast(jnp.min(allc), root=0)

    out = comm.run(body, jnp.asarray([n], jnp.int32))
    return int(out)
