"""Unified model: every assigned architecture is a sequence of *groups*,
each group a `lax.scan` over ``count`` structurally-identical superblocks
(1..6 sub-blocks each).  Heterogeneous layer patterns (gemma's 5 local :
1 global, llama-vision's 4 self : 1 cross, llama4's dense/MoE alternation,
xLSTM's mLSTM/sLSTM interleave) become superblock structure, so the HLO
stays O(1) in depth — essential for the 512-device dry-run sweep.

Public surface:
    Model(cfg, mesh)   .init  .train_loss  .logits  .prefill  .decode_step
                       .serve_step  .reset_cache_slots
                       .cache_specs  .param_specs (see partition.py)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from repro.configs.base import (ATTN, GLOBAL_WINDOW, HYMBA, MLSTM, SLSTM,
                                XATTN, ArchConfig)
from repro.models import blocks, cache as cache_lib
from repro.models.layers import (dense_init, rmsnorm, rmsnorm_init,
                                 softmax_xent_chunked, logits_for)
from repro.models.ssm import (mlstm_forward, mlstm_init, slstm_forward,
                              slstm_init, ssm_forward)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SubBlockDef:
    kind: str                     # attn | xattn | mlstm | slstm | hymba | enc
    window: int = GLOBAL_WINDOW
    theta: float = 10_000.0
    ffn: str = "dense"            # dense | moe | none
    d_ff: int = 0
    gated: bool = False           # tanh-gated cross-attn (llama-vision)
    use_window_array: bool = False


@dataclasses.dataclass(frozen=True)
class GroupDef:
    name: str
    count: int
    subs: Tuple[SubBlockDef, ...]
    window_array: Tuple[int, ...] = ()   # per-superblock window (hymba)


def build_groups(cfg: ArchConfig) -> Tuple[List[GroupDef], List[GroupDef]]:
    """Returns (decoder groups, encoder groups)."""
    enc: List[GroupDef] = []
    if cfg.encoder_layers:
        enc.append(GroupDef("enc", cfg.encoder_layers,
                            (SubBlockDef("enc", d_ff=cfg.d_ff),)))

    dec: List[GroupDef] = []
    w = cfg.sliding_window or GLOBAL_WINDOW
    if cfg.xlstm_pattern:
        pat = tuple(SubBlockDef(k, ffn="none") for k in cfg.xlstm_pattern)
        dec.append(GroupDef("xlstm", cfg.num_layers // len(pat), pat))
    elif cfg.family == "hybrid":
        dec.append(GroupDef(
            "hymba", cfg.num_layers,
            (SubBlockDef(HYMBA, d_ff=cfg.d_ff, use_window_array=True),),
            window_array=cfg.layer_windows()))
    elif cfg.encoder_layers:  # enc-dec decoder
        dec.append(GroupDef("dec", cfg.num_layers, (
            SubBlockDef(ATTN, ffn="none", theta=cfg.rope_theta),
            SubBlockDef(XATTN, d_ff=cfg.d_ff, theta=cfg.rope_theta))))
    elif cfg.xattn_every:
        n_super, rem = divmod(cfg.num_layers, cfg.xattn_every)
        assert rem == 0, cfg.name
        subs = tuple(SubBlockDef(ATTN, d_ff=cfg.d_ff, theta=cfg.rope_theta)
                     for _ in range(cfg.xattn_every - 1))
        subs += (SubBlockDef(XATTN, d_ff=cfg.d_ff, gated=True,
                             theta=cfg.rope_theta),)
        dec.append(GroupDef("vsuper", n_super, subs))
    elif cfg.num_experts:
        if cfg.first_dense_layers:
            dec.append(GroupDef("dense0", cfg.first_dense_layers, (
                SubBlockDef(ATTN, d_ff=cfg.dense_d_ff or cfg.d_ff,
                            theta=cfg.rope_theta),)))
        rest = cfg.num_layers - cfg.first_dense_layers
        if cfg.moe_every > 1:
            n_super, rem = divmod(rest, cfg.moe_every)
            assert rem == 0, cfg.name
            subs = tuple(SubBlockDef(ATTN, d_ff=cfg.dense_d_ff or cfg.d_ff,
                                     theta=cfg.rope_theta)
                         for _ in range(cfg.moe_every - 1))
            subs += (SubBlockDef(ATTN, ffn="moe", d_ff=cfg.d_ff,
                                 theta=cfg.rope_theta),)
            dec.append(GroupDef("msuper", n_super, subs))
        else:
            dec.append(GroupDef("moe", rest, (
                SubBlockDef(ATTN, ffn="moe", d_ff=cfg.d_ff,
                            theta=cfg.rope_theta),)))
    elif cfg.global_every:
        n_super, rem = divmod(cfg.num_layers, cfg.global_every)
        local = SubBlockDef(ATTN, window=w, d_ff=cfg.d_ff,
                            theta=cfg.rope_theta)
        glob = SubBlockDef(ATTN, window=GLOBAL_WINDOW, d_ff=cfg.d_ff,
                           theta=cfg.rope_theta_global or cfg.rope_theta)
        dec.append(GroupDef("gsuper", n_super,
                            (local,) * (cfg.global_every - 1) + (glob,)))
        if rem:
            dec.append(GroupDef("gtail", rem, (local,)))
    else:
        dec.append(GroupDef("dec", cfg.num_layers, (
            SubBlockDef(ATTN, window=w, d_ff=cfg.d_ff,
                        theta=cfg.rope_theta),)))
    return dec, enc


# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ArchConfig, mesh: Optional[Mesh] = None,
                 q_chunk: Optional[int] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.q_chunk = cfg.attn_q_chunk if q_chunk is None else q_chunk
        self.logits_dtype = jnp.bfloat16 \
            if cfg.attn_logits_dtype == "bf16" else jnp.float32
        self.ssm_scan_dtype = jnp.bfloat16 \
            if cfg.ssm_scan_dtype == "bf16" else jnp.float32
        self.mlstm_dtype = jnp.bfloat16 \
            if cfg.mlstm_dtype == "bf16" else jnp.float32
        self.dec_groups, self.enc_groups = build_groups(cfg)

    # --- moe plumbing -----------------------------------------------------
    def _moe_kwargs(self):
        mesh = self.mesh
        assert mesh is not None, "MoE archs need a mesh"
        names = mesh.axis_names
        batch_axes = tuple(a for a in ("pod", "data") if a in names)
        fsdp_axes: Tuple[str, ...] = ()
        if self.cfg.use_fsdp and "data" in names:
            fsdp_axes = ("data",)
            if self.cfg.use_pod_fsdp and "pod" in names:
                fsdp_axes = ("data", "pod")
        # only keep fsdp axes that divide the expert F dim
        f = self.cfg.d_ff
        kept = []
        for a in fsdp_axes:
            sz = mesh.shape[a]
            if f % sz == 0:
                kept.append(a)
                f //= sz
        return dict(top_k=self.cfg.top_k, num_experts=self.cfg.num_experts,
                    capacity_factor=self.cfg.capacity_factor, mesh=mesh,
                    batch_axes=batch_axes, fsdp_axes=tuple(kept),
                    comm=self.cfg.moe_comms,
                    gather_dtype=self.cfg.expert_gather_dtype)

    # --- init ---------------------------------------------------------------
    def _init_sub(self, key, s: SubBlockDef):
        cfg = self.cfg
        if s.kind == MLSTM:
            return mlstm_init(key, cfg.d_model, cfg.num_heads, cfg.head_dim)
        if s.kind == SLSTM:
            return slstm_init(key, cfg.d_model, cfg.num_heads, cfg.head_dim)
        k1, k2 = jax.random.split(key)
        if s.kind == HYMBA:
            p = blocks.hymba_init(k1, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim,
                                  cfg.ssm_d_inner, cfg.ssm_state)
        elif s.kind == XATTN:
            p = blocks.xattn_init(k1, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim, s.gated)
        else:  # attn / enc
            p = blocks.attn_init(k1, cfg.d_model, cfg.num_heads,
                                 cfg.num_kv_heads, cfg.head_dim)
        p.update(blocks.ffn_init(k2, cfg.d_model, s.d_ff, s.ffn,
                                 cfg.num_experts))
        return p

    def _init_group(self, key, g: GroupDef):
        def one(k):
            ks = jax.random.split(k, len(g.subs))
            return tuple(self._init_sub(ks[i], s)
                         for i, s in enumerate(g.subs))
        return jax.vmap(one)(jax.random.split(key, g.count))

    def init(self, key: Array):
        cfg = self.cfg
        ks = jax.random.split(key, 4 + len(self.dec_groups)
                              + len(self.enc_groups))
        params: Dict[str, Any] = {
            "emb": dense_init(ks[0], (cfg.vocab_size, cfg.d_model)),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["unemb"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size))
        i = 2
        for g in self.dec_groups:
            params[f"dec_{g.name}"] = self._init_group(ks[i], g)
            i += 1
        for g in self.enc_groups:
            params[f"enc_{g.name}"] = self._init_group(ks[i], g)
            i += 1
        if self.enc_groups:
            params["enc_norm"] = rmsnorm_init(cfg.d_model)
        if cfg.num_shared_experts:
            from repro.models.layers import swiglu_init
            params["shared_ffn"] = swiglu_init(
                ks[-1], cfg.d_model, cfg.d_ff * cfg.num_shared_experts)
        return params

    def init_abstract(self):
        return jax.eval_shape(self.init, jax.ShapeDtypeStruct((2,), jnp.uint32))

    # --- caches ---------------------------------------------------------------
    def _entry_shape(self, g: GroupDef, s: SubBlockDef, batch: int,
                     max_len: int,
                     paged: Optional[cache_lib.PageSpec] = None
                     ) -> Dict[str, Tuple]:
        cfg = self.cfg
        if s.kind == MLSTM:
            return {"C": ((g.count, batch, cfg.num_heads, cfg.head_dim,
                           cfg.head_dim), jnp.float32),
                    "n": ((g.count, batch, cfg.num_heads, cfg.head_dim),
                          jnp.float32),
                    "m": ((g.count, batch, cfg.num_heads), jnp.float32)}
        if s.kind == SLSTM:
            sh = (g.count, batch, cfg.num_heads, cfg.head_dim)
            return {k: (sh, jnp.float32) for k in ("c", "n", "h", "m")}
        out: Dict[str, Tuple] = {}
        if s.kind in (ATTN, HYMBA):
            wl = max_len if s.use_window_array else \
                cache_lib.cache_len_for(s.window, max_len)
            if paged is not None and wl >= max_len:
                # page exactly the entries whose dense form reserves the
                # full max_len; windowed rings are already proportional
                out["k"] = ((g.count, paged.num_blocks, paged.block_size)
                            + cache_lib.pool_row(cfg.num_kv_heads,
                                                 cfg.head_dim), jnp.bfloat16)
                out["v"] = out["k"]
                out["pos"] = ((batch, paged.logical_len(max_len)),
                              jnp.int32)
                out["btab"] = ((batch, paged.logical_blocks(max_len)),
                               jnp.int32)
            else:
                out["k"] = ((g.count, batch, wl, cfg.num_kv_heads,
                             cfg.head_dim), jnp.bfloat16)
                out["v"] = out["k"]
                out["pos"] = ((batch, wl), jnp.int32)
        if s.kind == XATTN:
            n = cfg.num_image_tokens or cfg.src_seq_len
            out["k"] = ((g.count, batch, n, cfg.num_kv_heads, cfg.head_dim),
                        jnp.bfloat16)
            out["v"] = out["k"]
        if s.kind == HYMBA:
            out["h"] = ((g.count, batch, cfg.ssm_d_inner, cfg.ssm_state),
                        jnp.float32)
            out["conv"] = ((g.count, batch, 3, cfg.ssm_d_inner), jnp.float32)
        return out

    def cache_specs(self, batch: int, max_len: int,
                    paged: Optional[cache_lib.PageSpec] = None):
        specs = {}
        for g in self.dec_groups:
            for si, s in enumerate(g.subs):
                ent = self._entry_shape(g, s, batch, max_len, paged)
                specs[f"{g.name}_{si}"] = {
                    k: jax.ShapeDtypeStruct(sh, dt)
                    for k, (sh, dt) in ent.items()}
        return specs

    def init_cache(self, batch: int, max_len: int,
                   paged: Optional[cache_lib.PageSpec] = None):
        def mk(sds):
            if sds.dtype == jnp.int32:
                return jnp.full(sds.shape, -1, jnp.int32)
            init = -jnp.inf if False else 0.0
            return jnp.zeros(sds.shape, sds.dtype)
        specs = self.cache_specs(batch, max_len, paged)
        out = jax.tree.map(mk, specs)
        # m-states start at -inf
        for name, ent in out.items():
            if "m" in ent and ent["m"].dtype == jnp.float32 \
                    and name.startswith(("xlstm",)):
                ent["m"] = jnp.full_like(ent["m"], -jnp.inf)
        return out

    # --- forward ---------------------------------------------------------------
    def _apply_sub(self, s: SubBlockDef, p, h, entry, pos, ctx, mode,
                   window_override=None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        dims = dict(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                    dh=cfg.head_dim)
        if s.kind == MLSTM:
            st = None if mode == "train" else (entry["C"], entry["n"],
                                               entry["m"])
            h, st2 = mlstm_forward(p, h, st, heads=cfg.num_heads,
                                   dh=cfg.head_dim,
                                   chunk=cfg.mlstm_chunk,
                                   compute_dtype=self.mlstm_dtype)
            new = None if mode == "train" else \
                {"C": st2[0], "n": st2[1], "m": st2[2]}
            return h, new, aux
        if s.kind == SLSTM:
            st = None if mode == "train" else (entry["c"], entry["n"],
                                               entry["h"], entry["m"])
            h, st2 = slstm_forward(p, h, st, heads=cfg.num_heads,
                                   dh=cfg.head_dim,
                                   compute_dtype=self.mlstm_dtype)
            new = None if mode == "train" else dict(
                zip(("c", "n", "h", "m"), st2))
            return h, new, aux
        if s.kind == "enc":
            from repro.models.layers import attention as attn_fn
            xn = rmsnorm(p["norm"], h)
            q, k, v = blocks._qkv(p, xn, xn, **dims)
            zeros = jnp.zeros(h.shape[:2], jnp.int32)
            o = attn_fn(q, k, v, zeros, zeros, causal=False,
                        q_chunk=self.q_chunk)
            B, C = h.shape[:2]
            h = h + o.reshape(B, C, -1) @ p["wo"]
            h, _ = blocks.apply_ffn(p, h, kind=s.ffn,
                                    moe_kwargs=None, mode=mode)
            return h, None, aux
        if s.kind == XATTN:
            media = ctx.get("media")
            if media is not None:
                mkv = blocks.media_kv_of(p, media, cfg.num_kv_heads,
                                         cfg.head_dim)
                new_media = mkv
            else:
                mkv = {"k": entry["k"], "v": entry["v"]}
                new_media = None
            o = blocks.cross_attention(p, h, mkv, **dims)
            if s.gated:
                o = o * jnp.tanh(p["gate_attn"]).astype(o.dtype)
            h = h + o
            moe_kwargs = self._moe_kwargs() if s.ffn == "moe" else None
            h2, aux = blocks.apply_ffn(p, h, kind=s.ffn,
                                       moe_kwargs=moe_kwargs, mode=mode)
            if s.gated and s.ffn != "none":
                h = h + (h2 - h) * jnp.tanh(p["gate_ffn"]).astype(h.dtype)
            else:
                h = h2
            new = None
            if mode != "train":
                new = {"k": new_media["k"] if new_media else entry["k"],
                       "v": new_media["v"] if new_media else entry["v"]}
            return h, new, aux
        # ATTN / HYMBA
        window = window_override if window_override is not None else s.window
        kv = None
        if mode != "train":
            kv = {"k": entry["k"], "v": entry["v"], "pos": entry["pos"]}
            if "btab" in entry:
                kv["btab"], kv["layer"] = entry["btab"], entry["layer"]
        o, new_kv = blocks.self_attention(
            p, h, pos, kv, window=window, theta=s.theta, mode=mode,
            q_chunk=self.q_chunk, logits_dtype=self.logits_dtype, **dims)
        if s.kind == HYMBA:
            xn = rmsnorm(p["norm"], h)
            so, st2 = ssm_forward(
                p["ssm"], xn,
                None if mode == "train" else (entry["h"], entry["conv"]),
                d_inner=cfg.ssm_d_inner, state=cfg.ssm_state,
                scan_dtype=self.ssm_scan_dtype)
            o = 0.5 * (rmsnorm(p["anorm"], o) + rmsnorm(p["snorm"], so))
        h = h + o
        moe_kwargs = self._moe_kwargs() if s.ffn == "moe" else None
        h, aux = blocks.apply_ffn(p, h, kind=s.ffn, moe_kwargs=moe_kwargs,
                                  mode=mode)
        new = None
        if mode != "train":
            new = dict(new_kv) if new_kv else {}
            if s.kind == HYMBA:
                new["h"], new["conv"] = st2[0], st2[1]
        return h, new, aux

    def _run_group(self, g: GroupDef, gparams, h, entries, pos, ctx, mode):
        """entries: dict sub_idx -> cache entry (with group-level 'pos'
        threaded in).  Returns (h, new entries, aux).

        Parameters, ring cache leaves and the window array are scanned
        per layer (``xs``/``ys``).  'pos' and 'btab' are group-level
        (identical for every layer) and threaded around the scan.  A
        paged entry's stacked pool 'k'/'v' rides in the carry whole and
        each layer updates it in place at its index (``xs['layer']``), so
        the pool is never sliced per layer, restacked or copied."""
        cfg = self.cfg
        train = mode == "train"
        subs = range(len(g.subs))
        ent = [{} if train else entries[si] for si in subs]
        paged = [not train and "btab" in e for e in ent]

        def scanned(e, pg):
            """The leaves of entry ``e`` that the scan takes per layer."""
            return {k: v for k, v in e.items() if k not in ("pos", "btab")
                    and not (pg and k in ("k", "v"))}

        cache_xs = tuple(scanned(e, pg) for e, pg in zip(ent, paged)) \
            if not train else ()
        pools = tuple({"k": e["k"], "v": e["v"]} if pg else None
                      for e, pg in zip(ent, paged)) if not train else ()
        xs = {"p": gparams, "c": cache_xs,
              "w": jnp.asarray(g.window_array, jnp.int32)
              if g.window_array else None,
              "layer": jnp.arange(g.count, dtype=jnp.int32)
              if any(paged) else None}

        def body(carry, xs):
            h, aux, pools = carry
            new_cs, new_pools = [], []
            for si, s in enumerate(g.subs):
                entry = None
                if not train:
                    entry = dict(xs["c"][si])
                    if "pos" in ent[si]:
                        entry["pos"] = ent[si]["pos"]
                    if paged[si]:
                        entry.update(pools[si], btab=ent[si]["btab"],
                                     layer=xs["layer"])
                h, new, a = self._apply_sub(s, xs["p"][si], h, entry, pos,
                                            ctx, mode,
                                            window_override=xs["w"])
                aux = aux + a
                if not train:
                    new = new or {}
                    new_pools.append({"k": new["k"], "v": new["v"]}
                                     if paged[si] else None)
                    new_cs.append(scanned(new, paged[si]))
            return (h, aux, tuple(new_pools)), tuple(new_cs)

        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_saveable
            body = jax.checkpoint(body, policy=policy)
        (h, aux, pools), new_cache_xs = lax.scan(
            body, (h, jnp.zeros((), jnp.float32), pools), xs)
        new_entries = {}
        if not train:
            for si in subs:
                new = dict(new_cache_xs[si])
                if paged[si]:
                    new.update(pools[si])
                    new["btab"] = ent[si]["btab"]  # host-leased, read-only
                if "pos" in ent[si]:
                    # group-level position update (same for all layers);
                    # masked scatter drops padded (-1) positions
                    new["pos"] = cache_lib.scatter_ring(
                        ent[si]["pos"], pos, pos)
                new_entries[si] = new
        return h, new_entries, aux

    def _encode(self, params, src_embeds):
        h = src_embeds
        for g in self.enc_groups:
            h, _, _ = self._run_group(g, params[f"enc_{g.name}"], h, {},
                                      jnp.zeros(h.shape[:2], jnp.int32),
                                      {}, "train")
        return rmsnorm(params["enc_norm"], h)

    def _backbone(self, params, h, pos, cache, ctx, mode):
        aux_total = jnp.zeros((), jnp.float32)
        new_cache = {}
        for g in self.dec_groups:
            entries = {}
            if mode != "train":
                entries = {si: cache[f"{g.name}_{si}"]
                           for si in range(len(g.subs))}
            h, new_entries, aux = self._run_group(
                g, params[f"dec_{g.name}"], h, entries, pos, ctx, mode)
            aux_total = aux_total + aux
            for si, ent in new_entries.items():
                new_cache[f"{g.name}_{si}"] = ent
        return rmsnorm(params["final_norm"], h), new_cache, aux_total

    def _unemb(self, params):
        if self.cfg.tie_embeddings:
            return params["emb"].T
        return params["unemb"]

    # --- public entry points ---------------------------------------------------
    def _causal_forward(self, params, tokens, ctx):
        """Cache-free forward: final-normed hidden states and aux loss."""
        B, S = tokens.shape
        h = jnp.take(params["emb"], tokens, axis=0)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        h, _, aux = self._backbone(params, h, pos, {}, ctx, "train")
        return h, aux

    def train_loss(self, params, batch) -> Tuple[Array, Dict[str, Array]]:
        """Loss for one microbatch: batch = {'tokens','labels', [extras]}."""
        h, aux = self._causal_forward(params, batch["tokens"],
                                      self._ctx_from(params, batch))
        loss = softmax_xent_chunked(h, self._unemb(params), batch["labels"])
        total = loss + 0.01 * aux
        return total, {"xent": loss, "aux": aux}

    def logits(self, params, tokens):
        """Cache-free causal forward over whole sequences (the reference
        the cached serving paths are checked against): tokens (B, S) ->
        float32 logits (B, S, V).  Positions past a sequence's end only
        see earlier tokens, so right-padding leaves the prefix exact."""
        h, _ = self._causal_forward(params, tokens, {"media": None})
        return logits_for(h, self._unemb(params))

    def _ctx_from(self, params, batch):
        ctx: Dict[str, Any] = {"media": None}
        if "image_embeds" in batch:
            ctx["media"] = batch["image_embeds"]
        if "src_embeds" in batch:
            ctx["media"] = self._encode(params, batch["src_embeds"])
        return ctx

    def extend(self, params, tokens, positions, cache, extras=None):
        """Process a chunk.  tokens: (B, C); positions: (B,) start positions.
        Returns (logits (B, C, V) of the last chunk only when C==1 else
        last-position logits, new cache)."""
        extras = extras or {}
        B, C = tokens.shape
        h = jnp.take(params["emb"], tokens, axis=0)
        pos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        ctx = self._ctx_from(params, extras)
        mode = "decode" if C == 1 else "chunk"
        h, new_cache, _ = self._backbone(params, h, pos, cache, ctx, mode)
        logits = logits_for(h[:, -1:], self._unemb(params))
        return logits, new_cache

    def prefill(self, params, tokens, extras=None, max_len: int = 0):
        """Chunked prefill over the full prompt.  Returns (last logits,
        filled cache).  ``max_len`` sizes the cache (>= prompt length +
        expected decode budget; defaults to the prompt length)."""
        cfg = self.cfg
        B, S = tokens.shape
        chunk = min(cfg.prefill_chunk, S)
        if S % chunk:
            chunk = S
        cache = self.init_cache(B, max(max_len, S))
        extras = extras or {}
        logits = None
        n = S // chunk
        ctx_extras = extras

        def step(carry, i):
            cache = carry
            tok = lax.dynamic_slice_in_dim(tokens, i * chunk, chunk, axis=1)
            start = jnp.full((B,), i * chunk, jnp.int32)
            lg, cache = self.extend(params, tok, start, cache, ctx_extras)
            return cache, lg

        cache, lgs = lax.scan(step, cache, jnp.arange(n))
        return lgs[-1], cache

    def decode_step(self, params, tokens, positions, cache):
        return self.extend(params, tokens, positions, cache, {})

    def serve_step(self, params, tokens, starts, lengths, cache):
        """One serving dispatch over a ragged batch.

        tokens: (B, C); starts: (B,) absolute position of each slot's
        first token; lengths: (B,) valid token count per slot (0 = idle
        slot).  Positions past ``lengths`` are masked to -1, so their
        tokens neither attend nor write to the cache.  Returns (logits
        (B, 1, V) at each slot's last valid token, new cache); idle
        slots' logits are garbage and must be ignored by the caller.
        """
        B, C = tokens.shape
        h = jnp.take(params["emb"], tokens, axis=0)
        off = jnp.arange(C, dtype=jnp.int32)[None]
        pos = jnp.where(off < lengths[:, None], starts[:, None] + off, -1)
        mode = "decode" if C == 1 else "chunk"
        h, new_cache, _ = self._backbone(params, h, pos, cache,
                                         {"media": None}, mode)
        last = jnp.clip(lengths - 1, 0, C - 1)
        hl = jnp.take_along_axis(
            h, last[:, None, None].astype(jnp.int32), axis=1)
        return logits_for(hl, self._unemb(params)), new_cache

    def reset_cache_slots(self, cache, mask):
        """Clear per-slot cache state where ``mask`` (B,) is True so the
        slot can be reused.  pos/btab go to -1; xLSTM stabilizer states
        ('m') to -inf; paged physical pools pass through untouched (their
        blocks are recycled through the host-side pool and overwritten on
        the next lease); everything else is zeroed.  Batch is axis 0 for
        pos/btab and axis 1 (after the layer-count axis) for the rest."""
        def reset_entry(ent):
            paged = "btab" in ent
            out = {}
            for k, v in ent.items():
                if k in ("pos", "btab"):
                    out[k] = jnp.where(mask[:, None],
                                       jnp.full_like(v, -1), v)
                elif paged and k in ("k", "v"):
                    out[k] = v
                else:
                    m = mask.reshape((1, -1) + (1,) * (v.ndim - 2))
                    fill = jnp.full_like(v, -jnp.inf) if k == "m" \
                        else jnp.zeros_like(v)
                    out[k] = jnp.where(m, fill, v)
            return out
        return {name: reset_entry(ent) for name, ent in cache.items()}
