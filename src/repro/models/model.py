"""Composable model definition covering all assigned architecture families:

dense decoder (GQA, optional sliding-window / local:global patterns),
MLA (DeepSeek), MoE (top-k + shared experts, optional expert parallelism),
Mamba-1 SSM, hybrid interleaves (Jamba), encoder–decoder (Whisper) and
stub-fronted multimodal backbones (InternVL, Whisper audio).

Depth heterogeneity is expressed as ``blocks = ((pattern, repeats), ...)``:
each *pattern* is a tuple of LayerSpec applied in order, and the pattern is
``lax.scan``-ned over ``repeats`` (one compile of the pattern per group — a
necessity at 61-layer/512-device scale).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "attn"          # 'attn' | 'mla' | 'mamba'
    window: Optional[int] = None  # None = global attention
    mlp: str = "dense"          # 'dense' | 'moe'
    cross_attn: bool = False    # enc-dec decoder layers


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    blocks: tuple  # ((pattern: tuple[LayerSpec, ...], repeats: int), ...)
    kind: str = "decoder"       # 'decoder' | 'encdec'
    n_enc_layers: int = 0
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    # --- MLA ---
    kv_lora: int = 0
    d_nope: int = 0
    d_rope: int = 0
    # --- SSM ---
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0
    # --- misc ---
    rope_theta: float = 10000.0
    use_rope: bool = True
    max_seq: int = 131072
    frontend: str = "none"      # 'none' | 'audio_stub' | 'vision_stub'
    frontend_len: int = 0
    tie_embeddings: bool = True
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: str = "none"         # 'none' | 'full' | 'dots'
    moe_ep: bool = False        # expert parallelism over the 'model' mesh axis
    scan_unroll: int = 1        # 1=scan, 0=full unroll (cost measurement)
    # --- distribution knobs (§Perf hillclimb; see launch/steps.py) ---
    seq_parallel: bool = False  # Megatron-SP: shard saved hiddens' seq axis
    seq_shard_kv: bool = False  # flash-decode: shard cache seq over 'model'
                                # when KV heads don't divide the TP degree
    serve_params_tp_only: bool = False  # serving: weights TP-sharded and
                                # replicated over DP (no per-step FSDP
                                # all-gather; right when params/TP fit HBM)

    @property
    def n_layers(self) -> int:
        return sum(len(p) * r for p, r in self.blocks)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank_eff(self) -> int:
        return self.dt_rank or max(1, self.d_model // 16)

    def param_count(self) -> tuple[int, int]:
        """(total, active) parameter counts — analytic, for 6ND roofline."""
        D, V = self.d_model, self.vocab_size
        emb = V * D
        total = emb if self.tie_embeddings else 2 * emb
        active = total
        for pattern, reps in self.blocks:
            for spec in pattern:
                t = a = 2 * D if spec.mlp != "none" else D  # norms
                if spec.kind == "attn":
                    t += D * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim
                    t += self.n_heads * self.head_dim * D
                    a = t
                elif spec.kind == "mla":
                    t += D * self.n_heads * (self.d_nope + self.d_rope)
                    t += D * (self.kv_lora + self.d_rope)
                    t += self.kv_lora * self.n_heads * (self.d_nope + self.head_dim)
                    t += self.n_heads * self.head_dim * D
                    a = t
                elif spec.kind == "mamba":
                    di = self.d_inner
                    t += D * 2 * di + self.d_conv * di + di * (self.dt_rank_eff + 2 * self.d_state)
                    t += self.dt_rank_eff * di + di * D
                    a = t
                if spec.mlp == "dense":
                    t += 3 * D * self.d_ff
                    a = t
                else:
                    routed = 3 * D * self.d_ff_expert
                    t += self.n_experts * routed + D * self.n_experts
                    a += self.top_k * routed + D * self.n_experts
                    if self.n_shared:
                        sh = 3 * D * (self.n_shared * self.d_ff_expert)
                        t += sh
                        a += sh
                if spec.cross_attn:
                    ca = D * 2 * self.n_heads * self.head_dim * 2 + D
                    t += ca
                    a += ca
                total += t * reps
                active += a * reps
        # encoder (whisper): plain dense attention layers
        if self.kind == "encdec":
            per = 2 * D + D * 3 * self.n_heads * self.head_dim + \
                self.n_heads * self.head_dim * D + 3 * D * self.d_ff
            total += per * self.n_enc_layers
            active += per * self.n_enc_layers
        return total, active


# ------------------------------------------------------------------ init
def _init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 8)
    p = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    if spec.mlp != "none":
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
    if spec.kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim, dtype)
    elif spec.kind == "mla":
        p["attn"] = L.init_mla(ks[0], cfg.d_model, cfg.n_heads,
                               kv_lora=cfg.kv_lora, d_nope=cfg.d_nope,
                               d_rope=cfg.d_rope, d_v=cfg.head_dim, dtype=dtype)
    elif spec.kind == "mamba":
        p["attn"] = L.init_mamba(ks[0], cfg.d_model, d_state=cfg.d_state,
                                 d_conv=cfg.d_conv, expand=cfg.expand,
                                 dt_rank=cfg.dt_rank_eff, dtype=dtype)
    if spec.mlp == "dense":
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif spec.mlp == "moe":
        p["mlp"] = L.init_moe(ks[1], cfg.d_model, cfg.d_ff_expert,
                              cfg.n_experts, cfg.n_shared, cfg.d_ff_expert, dtype)
    if spec.cross_attn:
        p["normc"] = jnp.zeros((cfg.d_model,), dtype)
        p["cross"] = L.init_attention(ks[2], cfg.d_model, cfg.n_heads,
                                      cfg.n_heads, cfg.head_dim, dtype)
    return p


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {
        "embed": L._init(keys[0], (cfg.vocab_size, cfg.d_model),
                         scale=0.02, dtype=dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._init(keys[1], (cfg.d_model, cfg.vocab_size),
                                    dtype=dtype)
    if not cfg.use_rope:
        params["pos_embed"] = L._init(keys[2], (cfg.max_seq, cfg.d_model),
                                      scale=0.02, dtype=dtype)

    def init_group(key, pattern, repeats):
        def one(k):
            kk = jax.random.split(k, len(pattern))
            return tuple(_init_layer(kk[i], spec, cfg, dtype)
                         for i, spec in enumerate(pattern))
        return jax.vmap(one)(jax.random.split(key, repeats))

    gkeys = jax.random.split(keys[3], len(cfg.blocks))
    params["groups"] = [init_group(gkeys[i], pattern, reps)
                        for i, (pattern, reps) in enumerate(cfg.blocks)]

    if cfg.kind == "encdec":
        enc_spec = LayerSpec(kind="attn", window=None, mlp="dense")
        ekeys = jax.random.split(keys[4], cfg.n_enc_layers)
        params["enc"] = {
            "groups": [jax.vmap(lambda k: (_init_layer(k, enc_spec, cfg, dtype),))(ekeys)],
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
            "pos_embed": L._init(keys[5], (cfg.max_seq, cfg.d_model),
                                 scale=0.02, dtype=dtype),
        }
    return params


# ------------------------------------------------------------------ cache
def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16,
               enc_len: int = 0):
    """Decode cache pytree mirroring params['groups'] structure."""
    neg = jnp.iinfo(jnp.int32).max

    def layer_cache(spec: LayerSpec):
        if spec.kind == "mamba":
            c = {"conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
                 "h": jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32)}
        elif spec.kind == "mla":
            c = {"c_kv": jnp.zeros((batch, s_max, cfg.kv_lora), dtype),
                 "k_rope": jnp.zeros((batch, s_max, cfg.d_rope), dtype),
                 "pos_k": jnp.full((batch, s_max), neg, jnp.int32)}
        else:
            Ck = min(s_max, spec.window) if spec.window else s_max
            c = {"k": jnp.zeros((batch, Ck, cfg.n_kv_heads, cfg.head_dim), dtype),
                 "v": jnp.zeros((batch, Ck, cfg.n_kv_heads, cfg.head_dim), dtype),
                 "pos_k": jnp.full((batch, Ck), neg, jnp.int32)}
        if spec.cross_attn:
            c["ck"] = jnp.zeros((batch, enc_len, cfg.n_heads, cfg.head_dim), dtype)
            c["cv"] = jnp.zeros((batch, enc_len, cfg.n_heads, cfg.head_dim), dtype)
        return c

    def rep(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)

    return [rep(tuple(layer_cache(s) for s in pattern), reps)
            for pattern, reps in cfg.blocks]


# ------------------------------------------------------------------ forward
def _apply_layer(lp, spec: LayerSpec, cfg: ModelConfig, x, positions,
                 cache=None, decode=False, enc_out=None, mesh=None):
    h = L.rms_norm(x, lp["norm1"])
    if spec.kind == "mamba":
        out, new_c = L.mamba_apply(lp["attn"], h, d_state=cfg.d_state,
                                   d_conv=cfg.d_conv, cache=cache, decode=decode)
    elif spec.kind == "mla":
        out, new_c = L.mla_attention(lp["attn"], h, positions,
                                     d_nope=cfg.d_nope, d_rope=cfg.d_rope,
                                     rope_theta=cfg.rope_theta,
                                     cache=cache, decode=decode)
    else:
        out, new_c = L.attention(lp["attn"], h, positions,
                                 n_rep=cfg.n_heads // cfg.n_kv_heads,
                                 window=spec.window, rope_theta=cfg.rope_theta,
                                 use_rope=cfg.use_rope, cache=cache,
                                 decode=decode)
    x = x + out

    if spec.cross_attn:
        h = L.rms_norm(x, lp["normc"])
        if decode:
            ck, cv = cache["ck"], cache["cv"]
            q = jnp.einsum("bsd,dhk->bshk", h, lp["cross"]["wq"])
            s = jnp.einsum("bshk,bthk->bsht", q, ck,
                           preferred_element_type=jnp.float32)
            s = s / np.sqrt(cfg.head_dim)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bsht,bthk->bshk", p, cv).astype(h.dtype)
            out = jnp.einsum("bshk,hkd->bsd", o, lp["cross"]["wo"])
            new_c = dict(new_c or {}, ck=ck, cv=cv)
        else:
            q = jnp.einsum("bsd,dhk->bshk", h, lp["cross"]["wq"])
            ck = jnp.einsum("btd,dhk->bthk", enc_out, lp["cross"]["wk"])
            cv = jnp.einsum("btd,dhk->bthk", enc_out, lp["cross"]["wv"])
            s = jnp.einsum("bshk,bthk->bsht", q, ck,
                           preferred_element_type=jnp.float32)
            s = s / np.sqrt(cfg.head_dim)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bsht,bthk->bshk", p, cv).astype(h.dtype)
            out = jnp.einsum("bshk,hkd->bsd", o, lp["cross"]["wo"])
            if new_c is not None:
                new_c = dict(new_c, ck=ck.astype(x.dtype), cv=cv.astype(x.dtype))
        x = x + out

    if spec.mlp != "none":
        h = L.rms_norm(x, lp["norm2"])
        if spec.mlp == "dense":
            out = L.mlp_apply(lp["mlp"], h)
        else:
            out = _moe(lp["mlp"], h, cfg, mesh)
            if "shared" in lp["mlp"]:
                out = out + L.mlp_apply(lp["mlp"]["shared"], h)
        x = x + out
    return x, new_c


def _moe(mp, h, cfg: ModelConfig, mesh):
    routed = {k: mp[k] for k in ("router", "w_gate", "w_up", "w_down")}
    if cfg.moe_ep and mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P
        ep = mesh.shape["model"]
        dp = tuple(a for a in mesh.axis_names if a != "model")
        fn = partial(L.moe_apply, top_k=cfg.top_k,
                     capacity_factor=cfg.capacity_factor,
                     ep_axis="model", ep_size=ep)
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=({"router": P(), "w_gate": P("model"), "w_up": P("model"),
                       "w_down": P("model")}, P(dp)),
            out_specs=P(dp),
            check_vma=False,
        )(routed, h)
    return L.moe_apply(routed, h, top_k=cfg.top_k,
                       capacity_factor=cfg.capacity_factor)


def _run_groups(groups_params, blocks, cfg, x, positions, caches=None,
                decode=False, enc_out=None, mesh=None, want_cache=False):
    """Scan each homogeneous (pattern × repeats) group."""
    new_caches = []
    for gi, (pattern, reps) in enumerate(blocks):
        gp = groups_params[gi]
        cache_g = caches[gi] if caches is not None else None

        def body(xc, inp):
            x = xc
            lps, cs = inp if cache_g is not None else (inp, None)
            ncs = []
            for pi, spec in enumerate(pattern):
                c = cs[pi] if cs is not None else None
                x, nc = _apply_layer(lps[pi], spec, cfg, x, positions,
                                     cache=c, decode=decode, enc_out=enc_out,
                                     mesh=mesh)
                ncs.append(nc)
            y = tuple(ncs) if (want_cache or decode) else None
            return x, y

        if cfg.seq_parallel:
            # Megatron-SP: pin the layer-boundary hidden (what remat saves
            # and the scan carries) to a sequence-sharded layout.
            from repro.launch.shardctx import constrain
            inner_body = body

            def body(xc, inp):  # noqa: F811
                x, y = inner_body(xc, inp)
                return constrain(x, "hidden_sp"), y

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        elif cfg.remat == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        xs = (gp, cache_g) if cache_g is not None else gp
        unroll = reps if cfg.scan_unroll == 0 else min(cfg.scan_unroll, reps)
        x, ys = lax.scan(body, x, xs, unroll=unroll)
        new_caches.append(ys)
    return x, (new_caches if (want_cache or decode) else None)


def forward(params, cfg: ModelConfig, tokens=None, *, embeds=None,
            positions=None, caches=None, mode: str = "train",
            enc_frames=None, mesh=None):
    """Forward pass.

    mode='train'   : full-sequence causal logits.
    mode='prefill' : as train, but fills and returns the decode cache.
    mode='decode'  : tokens (B,1) against ``caches``; positions (B,1).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    decode = mode == "decode"
    want_cache = mode == "prefill"

    parts = []
    if embeds is not None:  # vision stub prefix (B, Lv, D)
        parts.append(embeds.astype(cdt))
    if tokens is not None:
        parts.append(params["embed"].astype(cdt)[tokens])
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    from repro.launch.shardctx import constrain
    x = constrain(x, "hidden")
    B, S, _ = x.shape

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if not cfg.use_rope:
        pe = params["pos_embed"].astype(cdt)[positions]
        x = x + pe

    enc_out = None
    if cfg.kind == "encdec" and not decode:
        ef = enc_frames.astype(cdt)
        Te = ef.shape[1]
        epos = jnp.broadcast_to(jnp.arange(Te, dtype=jnp.int32), (B, Te))
        e = ef + params["enc"]["pos_embed"].astype(cdt)[epos]
        enc_blocks = (((LayerSpec(kind="attn", window=None, mlp="dense"),),
                       cfg.n_enc_layers),)
        # encoder is bidirectional: give every position visibility via a
        # window=None non-causal path — reuse attention with positions all
        # equal so the causal mask passes everywhere.
        e, _ = _run_groups(params["enc"]["groups"], enc_blocks, cfg, e,
                           jnp.zeros((B, Te), jnp.int32), mesh=mesh)
        enc_out = L.rms_norm(e, params["enc"]["final_norm"])

    x, new_caches = _run_groups(params["groups"], cfg.blocks, cfg, x,
                                positions, caches=caches, decode=decode,
                                enc_out=enc_out, mesh=mesh,
                                want_cache=want_cache)

    x = L.rms_norm(x, params["final_norm"])
    x = constrain(x, "hidden")
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cdt)
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    logits = constrain(logits, "logits")
    if decode or want_cache:
        return logits, new_caches
    return logits


def lm_loss(params, cfg: ModelConfig, batch, mesh=None):
    """Next-token cross entropy. batch['tokens']: (B, S+1) int32."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    kw = {}
    if cfg.frontend == "vision_stub":
        kw["embeds"] = batch["patch_embeds"]
    if cfg.kind == "encdec":
        kw["enc_frames"] = batch["audio_frames"]
    logits = forward(params, cfg, inputs, mesh=mesh, **kw)
    if cfg.frontend == "vision_stub":  # text logits follow the vision prefix
        logits = logits[:, -targets.shape[1]:]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    loss = jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss
