"""Serving cells: the program's ``Engine.run_trace`` under a traffic mix.

Set-up makes the weights on the device from the seed, builds the engine
with the configuration's serving settings and serves one short request,
which compiles (or loads) the chunk tick, the decode tick and the slot
reset.  The window then drives ``run_trace`` over the mix; the
benchmark's wrapper on ``sched.plan`` ends it at ``--seconds`` by
raising, so no drain is timed and a request in flight counts only the
tokens it produced inside the window.

The benchmark's host spans (``cb:plan``, ``cb:dispatch.<kind>``,
``cb:finish``, ``cb:admit``) sit around the engine's calls into each
layer; they are ``TraceAnnotation``s, on the device trace's clock.

``correct``: once the window has closed, a sample of the requests it
finished, drawn from the seed and holding the longest, is run through
the configuration's float32 reference, teacher-forced on prompt and
served tokens.  The number compared is the mean, over the served tokens,
of the gap by which a served token's reference logit lies below that
position's reference maximum.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
from typing import Dict, List, Tuple

import jax
import numpy as np

from chipbench.lib import trace as trace_lib
from chipbench.lib import traffic as traffic_lib
from chipbench.lib.harness import (BENCH, OUT, Check, CompileCount, Outcome,
                                   Phases, jax_key, load_module, log,
                                   peak_bytes)

from repro.configs.base import ArchConfig, get_config
from repro.launch.mesh import mesh_for_devices
from repro.serve import Engine, Request


DISPATCH_PROGRAM = "_dispatch_body"       # the engine's jitted tick


class WindowClosed(Exception):
    """Raised by the plan wrapper once the window has run out."""


@dataclasses.dataclass
class Tick:
    kind: str
    t: float                          # host seconds since the window began
    host_s: float                     # wall time of the dispatch call
    rows: List[Tuple[int, int]]       # (start, length) of rows with tokens


class BenchEngine(Engine):
    """The engine with the benchmark's spans and clocks around its
    layers; behaviour unchanged."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.window_s = float("inf")
        self.on_plan = None
        inner = self.sched.plan

        def plan():
            now = self._now()
            if now >= self.window_s:
                raise WindowClosed
            if self.on_plan is not None:
                self.on_plan(now)
            with jax.profiler.TraceAnnotation("cb:plan"):
                return inner()

        self.sched.plan = plan
        self.reset_clocks()

    def reset_clocks(self) -> None:
        self.ticks: List[Tick] = []
        self.token_times: Dict[int, List[float]] = {}
        self.admitted: Dict[int, float] = {}
        self.finished: Dict[int, float] = {}

    def _dispatch(self, plan):
        t = self._now()
        with jax.profiler.TraceAnnotation(f"cb:dispatch.{plan.kind}"):
            nxt = super()._dispatch(plan)
        rows = [(int(s), int(n)) for s, n in zip(plan.starts, plan.lengths)
                if n > 0]
        self.ticks.append(Tick(plan.kind, t, self._now() - t, rows))
        return nxt

    def _finish(self, plan, nxt):
        with jax.profiler.TraceAnnotation("cb:finish"):
            return super()._finish(plan, nxt)

    def _record(self, slot, epoch, gidx, tok, now):
        rid = self.sched.states[slot].rid
        self.token_times.setdefault(rid, []).append(now)
        super()._record(slot, epoch, gidx, tok, now)

    def _admit_one(self, req, arrival_s):
        self.admitted[req.rid] = self._now()
        with jax.profiler.TraceAnnotation("cb:admit"):
            super()._admit_one(req, arrival_s)

    def _finalize(self, rid, now):
        self.finished[rid] = now
        super()._finalize(rid, now)


# ------------------------------------------------------------------ set-up


def arch_config(conf: dict) -> ArchConfig:
    """The program's config for this benchmark configuration: the
    registry entry with every size the file states."""
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    sizes = {k: v for k, v in conf["model"].items() if k in fields}
    return dataclasses.replace(
        base, prefill_chunk=conf["serving"]["prefill_chunk"], **sizes)


def program_params(w: dict) -> dict:
    """The benchmark's weights in the program's parameter layout (the
    same arrays, no copy)."""
    layer = {"norm": {"scale": w["attn_norm"]}, "wq": w["wq"],
             "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
             "fnorm": {"scale": w["ffn_norm"]},
             "ffn": {"w1": w["w1"], "w3": w["w3"], "w2": w["w2"]}}
    return {"emb": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "unemb": w["lm_head"], "dec_dec": (layer,)}


def reference_of(conf: dict):
    return load_module(BENCH / "references" / f"{conf['reference']}.py")


def make_weights(conf: dict, seed: int):
    ref = reference_of(conf)
    model = conf["model"]
    return jax.jit(lambda k: ref.init_weights(model, k))(jax_key(seed, 0))


def build(conf: dict, weights, devices) -> BenchEngine:
    s = conf["serving"]
    eng = BenchEngine(arch_config(conf), mesh_for_devices(len(devices)),
                      slots=s["slots"], max_len=s["max_len"],
                      block_size=s["block_size"], cache_mode="paged")
    eng.load(program_params(weights))
    return eng


def warm_up(eng: BenchEngine) -> None:
    """Two short requests, one after the other: the chunk tick, the
    decode tick and (between them) the slot reset, at the shapes the
    window uses."""
    for rid in (-1, -2):
        eng.run_trace([Request(rid=rid, prompt=np.arange(8, dtype=np.int32),
                               max_new_tokens=3)], [0.0])
    jax.block_until_ready(eng.cache)
    eng.reset_clocks()


def to_requests(reqs) -> List[Request]:
    return [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature) for r in reqs]


# ------------------------------------------------------------------ window


class Tracer:
    """Starts the profiler ``trace_s`` before the window closes and
    stops it after; the span ``cb:traced`` marks what was traced."""

    def __init__(self, eng: BenchEngine, seconds: float, trace_s: float,
                 out_dir):
        self.eng, self.out_dir = eng, str(out_dir)
        self.start_at = max(0.0, seconds - trace_s)
        self.span = None
        self.t_host = None

    def __call__(self, now: float) -> None:
        if self.span is None and now >= self.start_at:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            jax.profiler.start_trace(self.out_dir)
            self.span = jax.profiler.TraceAnnotation("cb:traced")
            self.span.__enter__()
            self.t_host = self.eng._now()

    def stop(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def serve_window(eng: BenchEngine, reqs, seconds: float, tracer=None):
    """Drive ``run_trace`` for ``seconds``; returns the requests due."""
    eng.window_s = seconds
    eng.on_plan = tracer
    try:
        eng.run_trace(to_requests(reqs), [r.arrival_s for r in reqs])
    except WindowClosed:
        pass
    jax.block_until_ready((eng.cache, eng.next_buf))
    if hasattr(tracer, "stop"):
        tracer.stop()
    eng.on_plan = None
    return [r for r in reqs if r.arrival_s < seconds]


def window_metrics(eng: BenchEngine, due, seconds: float) -> Dict[str, float]:
    times = {rid: [t for t in ts if t <= seconds]
             for rid, ts in eng.token_times.items()}
    tokens = sum(len(ts) for ts in times.values())
    ttft = []
    for r in due:
        ts = times.get(r.rid)
        ttft.append((ts[0] if ts else seconds) - r.arrival_s)
    itl = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])]
    return {"tokens_per_s": tokens / seconds,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3
            if ttft else float("nan"),
            "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3
            if itl else float("nan"),
            "tokens": tokens, "itl_count": len(itl)}


# ---------------------------------------------------------------- correct


def served_sample(eng: BenchEngine, reqs, seed: int, rows: int):
    """(request, served tokens) pairs: the longest finished request and
    others drawn from the seed; requests still in flight fill in only
    where too few finished."""
    by_id = {r.rid: r for r in reqs}
    done = [(by_id[rid], np.asarray(t, np.int32))
            for rid, t in eng._done.items() if rid in by_id and len(t)]
    live = [(by_id[rid], np.asarray(req.out_tokens, np.int32))
            for rid, req in eng.requests.items()
            if rid in by_id and req.out_tokens]
    rng = np.random.default_rng([seed, 2])
    out = []
    for group in (done, live):
        if not group or len(out) >= rows:
            continue
        group = sorted(group, key=lambda p: -(len(p[0].prompt) + len(p[1])))
        out.append(group[0])
        rest = group[1:]
        pick = rng.permutation(len(rest))[:rows - len(out)]
        out.extend(rest[i] for i in pick)
    return out


def reference_gaps(conf: dict, weights, sample, n: int,
                   quant: bool = False):
    """Per request, the gaps of the served tokens under the float32
    reference (and, with ``quant``, the gaps of the tokens the control
    puts first).  One padded batch of ``check_rows`` x ``max_len``
    sequences, read at ``n`` positions each (the mix's longest output),
    so every run has the same shapes."""
    ref = reference_of(conf)
    rows = conf["check"]["rows"]
    T = conf["serving"]["max_len"]
    toks = np.zeros((rows, T), np.int32)
    at = np.zeros((rows, n), np.int32)
    for b in range(rows):
        r, s = sample[min(b, len(sample) - 1)]
        seq = np.concatenate([r.prompt, s[:-1]])
        toks[b, :len(seq)] = seq
        at[b] = len(r.prompt) - 1 + np.minimum(np.arange(n), len(s) - 1)
    model = {k: v for k, v in conf["model"].items()
             if isinstance(v, (int, float))}
    lg = ref.logits_at(model, weights, toks, at)
    ctl = ref.logits_at(model, weights, toks, at, quant=True) if quant \
        else None
    lg = np.asarray(lg)
    top = lg.max(-1)
    served, control = [], []
    for b, (r, s) in enumerate(sample):
        m = len(s)
        served.append(top[b, :m] - lg[b, np.arange(m), s])
        if ctl is not None:
            pick = np.asarray(ctl[b, :m]).argmax(-1)
            control.append(top[b, :m] - lg[b, np.arange(m), pick])
    return served, control


def gap_stats(gaps) -> dict:
    """The mean gap over every compared token (the number ``correct``
    compares), with the widest and the count of tokens that are not the
    reference's best, for the record.  No token compared reads inf."""
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    if not allg.size:
        return {"mean": float("inf"), "widest": float("inf"),
                "flipped": 0, "tokens": 0}
    return {"mean": float(allg.mean()), "widest": float(allg.max()),
            "flipped": int((allg > 0).sum()), "tokens": int(allg.size)}


# ---------------------------------------------------------------- the run


@dataclasses.dataclass
class ServeRun:
    """What the per-layer readers read."""
    model: dict
    peaks: dict
    ticks: List[Tick]
    inflight: List[Tuple[float, float]]   # host seconds, >= 1 request
    trace: trace_lib.Trace = None
    traced_from_s: float = float("inf")   # host seconds
    t_offset_ns: float = 0.0              # trace ns = host s * 1e9 + this
    lo_ns: float = 0.0                    # the traced window
    hi_ns: float = 0.0

    def host_to_ns(self, t: float) -> float:
        return t * 1e9 + self.t_offset_ns

    def traced_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.t >= self.traced_from_s]

    def untraced_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.t < self.traced_from_s]

    def tick_programs(self) -> List[Tuple[Tick, trace_lib.Event]]:
        """Each traced tick with the device's execution of its program.
        The device runs the engine's dispatches in order and every
        traced one has finished before the trace stops, so the two lists
        are aligned from their ends."""
        if self.trace is None or not self.trace.modules:
            return []
        dev = self.trace.devices[0]
        runs = [e for e in self.trace.modules.get(dev, [])
                if DISPATCH_PROGRAM in e.name
                and self.lo_ns <= e.start < self.hi_ns]
        ticks = self.traced_ticks()
        n = min(len(runs), len(ticks))
        return list(zip(ticks[len(ticks) - n:], runs[len(runs) - n:]))


def inflight_intervals(eng: BenchEngine, seconds: float):
    spans = sorted((t, eng.finished.get(rid, seconds))
                   for rid, t in eng.admitted.items())
    out: List[List[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def run(cell, args, devices, peaks, phases: Phases) -> Outcome:
    conf, mix = cell.config, cell.traffic
    with CompileCount() as setup_compiles:
        weights = jax.block_until_ready(make_weights(conf, args.seed))
        phases.mark("weights")
        eng = build(conf, weights, devices)
        phases.mark("build")
        warm_up(eng)
        phases.mark("warm_up")
    reqs = traffic_lib.generate(mix, args.seed, args.seconds,
                                conf["model"]["vocab_size"])
    tracer = None
    if args.trace:
        tracer = Tracer(eng, args.seconds, conf["trace_seconds"],
                        OUT / "trace" / cell.name)
    phases.mark("traffic")
    setup_s = phases.total
    log(f"set-up {setup_s:.2f} s ({setup_compiles.count} programs "
        f"compiled or loaded); {len(reqs)} requests in the mix")
    with CompileCount() as window_compiles:
        due = serve_window(eng, reqs, args.seconds, tracer)
    e2e = window_metrics(eng, due, args.seconds)
    e2e["setup_s"] = setup_s
    log(f"window: {len(due)} requests due, {e2e['tokens']} tokens, "
        f"{len(eng.ticks)} ticks ({sum(t.kind == 'chunk' for t in eng.ticks)}"
        f" chunk), {len(eng.finished)} finished, "
        f"{window_compiles.count} compiles inside the window")
    mem = peak_bytes(devices)
    srun = ServeRun(conf["model"], peaks, list(eng.ticks),
                    inflight_intervals(eng, args.seconds))
    sample = served_sample(eng, reqs, args.seed, conf["check"]["rows"])
    del eng
    gc.collect()
    outcome = Outcome(e2e, [], len(due), 0, mem, srun,
                      notes={"setup_phases_s": phases.seconds,
                             "compiles_in_window": window_compiles.count})
    served = reference_gaps(conf, weights, sample,
                            mix["output"]["max"])[0] if sample else []
    gaps = gap_stats(served)
    log(f"reference: {len(sample)} requests, {gaps['tokens']} served "
        f"tokens; widest gap {gaps['widest']}, {gaps['flipped']} tokens "
        f"off the reference's best")
    outcome.checks.append(Check("mean_logit_gap", gaps["mean"],
                                conf["check"]["max_mean_logit_gap"]))
    if tracer is not None and tracer.span is not None:
        read_trace(srun, tracer, outcome)
    return outcome


def read_trace(srun: ServeRun, tracer: Tracer, outcome: Outcome) -> None:
    tr = trace_lib.read_dir(tracer.out_dir)
    span = trace_lib.spans_named(tr, "cb:traced")[-1]
    srun.trace = tr
    srun.traced_from_s = tracer.t_host
    srun.t_offset_ns = span.start - tracer.t_host * 1e9
    srun.lo_ns, srun.hi_ns = span.start, span.end
    outcome.window_s = span.dur / 1e9
    outcome.busy_s = trace_lib.busy_ns(tr, span.start, span.end) / 1e9
    outcome.breakdown = {
        "device_ops": trace_lib.top_ops(tr, span.start, span.end),
        "idle_gaps": trace_lib.idle_gaps(tr, span.start, span.end)}
    shutil.rmtree(tracer.out_dir, ignore_errors=True)
