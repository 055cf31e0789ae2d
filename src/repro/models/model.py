"""Model assembly: decoder-only / MoE / SSM / hybrid / encoder-decoder stacks
with scan-over-layers, remat, and train / prefill / decode entry points.

The serving steps name their parts with ``jax.named_scope`` (``attention``:
projections, KV writes and reads, attention and output projection; ``ffn``;
``lm_head``), so that a profiler trace can tell the parts of a step apart.
Scopes change op metadata only, not the computation.

All assigned architectures run through this one implementation, selected by
:class:`ArchConfig`.  Params are explicit pytrees; layers are stacked along a
leading axis and iterated with ``lax.scan`` so the lowered HLO stays small for
72-layer / 398B-parameter configs.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.ctx import shard_hint
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models.config import ArchConfig, ShapeSpec
from repro.utils import dtype_of

Params = Any
Cache = Any

MOE_AUX_COEF = 0.01
ZLOSS_COEF = 1e-4


# ----------------------------------------------------------------- sublayers

def _init_sublayer(cfg: ArchConfig, rng, kind: str, ffn_kind: str, dtype,
                   pad_experts_to: int = 0):
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg, cfg.d_model, dtype)}
    if kind == "attn":
        p["attn"] = L.init_attention(cfg, ks[0], dtype)
    else:
        p["ssm"] = M.init_mamba_block(cfg, ks[0], dtype)
    if ffn_kind == "dense":
        p["ln2"] = L.init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = L.init_ffn(cfg, ks[1], dtype)
    elif ffn_kind == "moe":
        p["ln2"] = L.init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = L.init_moe(cfg, ks[1], dtype, pad_experts_to=pad_experts_to)
    return p


def _apply_ffn_part(cfg: ArchConfig, p, x, ffn_kind: str, moe_groups: int = 1):
    aux = jnp.zeros((), jnp.float32)
    if ffn_kind == "none":
        return x, aux
    with jax.named_scope("ffn"):
        h = L.apply_norm(cfg, p["ln2"], x)
        if ffn_kind == "moe":
            out, aux = L.apply_moe(cfg, p["ffn"], h, groups=moe_groups)
        else:
            out = L.apply_ffn(cfg, p["ffn"], h)
        return x + out, aux


def _apply_sublayer_full(cfg: ArchConfig, p, x, positions, kind: str,
                         ffn_kind: str, *, causal: bool, want_cache: bool,
                         attn_impl: str, attn_chunk: int, ssd_chunk: int,
                         moe_groups: int = 1):
    """Full-sequence (train/prefill) sublayer.  Returns (x, cache, aux)."""
    h = L.apply_norm(cfg, p["ln1"], x)
    cache = None
    if kind == "attn":
        with jax.named_scope("attention"):
            q, k, v = L._project_qkv(cfg, p["attn"], h, positions)
            if attn_impl == "chunked":
                attn = L.chunked_attention(cfg, q, k, v, causal=causal,
                                           q_chunk=attn_chunk,
                                           kv_chunk=attn_chunk)
            else:
                attn = L.full_attention(cfg, q, k, v, causal=causal)
            B, S = x.shape[:2]
            out = attn.reshape(B, S, -1) @ p["attn"]["wo"]
        if want_cache:
            cache = {"k": k, "v": v}
    else:
        if want_cache:
            out, state = M.mamba_block(cfg, p["ssm"], h, chunk=ssd_chunk,
                                       return_state=True)
            cache = state
        else:
            out = M.mamba_block(cfg, p["ssm"], h, chunk=ssd_chunk)
    x = x + out
    x, aux = _apply_ffn_part(cfg, p, x, ffn_kind, moe_groups)
    x = shard_hint(x, "batch", None, None)
    return x, cache, aux


def _attn_decode(cfg: ArchConfig, p_attn, h, k_cache, v_cache, lengths):
    """h: (B,1,D).  Writes new kv at index `lengths`, attends to lengths+1."""
    B = h.shape[0]
    with jax.named_scope("attention"):
        q, k, v = L._project_qkv(cfg, p_attn, h, lengths[:, None])
        rows = jnp.arange(B)
        k_cache = k_cache.at[rows, lengths].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, lengths].set(v[:, 0].astype(v_cache.dtype))
        attn = L.decode_attention(cfg, q[:, 0], k_cache, v_cache, lengths + 1)
        out = attn.reshape(B, -1) @ p_attn["wo"]
    return out[:, None, :], k_cache, v_cache


def _apply_sublayer_decode(cfg: ArchConfig, p, x, lengths, kind: str,
                           ffn_kind: str, cache, moe_groups: int = 1):
    """Single-token decode sublayer.  x: (B,1,D)."""
    h = L.apply_norm(cfg, p["ln1"], x)
    if kind == "attn":
        out, k_c, v_c = _attn_decode(cfg, p["attn"], h, cache["k"], cache["v"], lengths)
        new_cache = {"k": k_c, "v": v_c}
    else:
        out2d, new_cache = M.mamba_decode_step(cfg, p["ssm"], h[:, 0, :], cache)
        out = out2d[:, None, :]
    x = x + out
    x, _ = _apply_ffn_part(cfg, p, x, ffn_kind, moe_groups)
    return x, new_cache


# ------------------------------------------------------------------- hybrid

# Jamba group layout (group_size = attn_every = 8):
#   j: 0        1        2        3         4        5        6        7
#   mixer: ssm  ssm      ssm      attn      ssm      ssm      ssm      ssm
#   ffn:  dense moe      dense    moe       dense    moe      dense    moe
# Stacks: "sd" = ssm+dense (j 0,2,4,6), "sm" = ssm+moe (j 1,5,7), "am" = attn+moe (j 3)

_HYBRID_ORDER = [("sd", 0), ("sm", 0), ("sd", 1), ("am", 0),
                 ("sd", 2), ("sm", 1), ("sd", 3), ("sm", 2)]
_HYBRID_SSM_J = [0, 1, 2, 4, 5, 6, 7]          # j indices that are ssm mixers


def _hybrid_group_structure(cfg: ArchConfig):
    gs = cfg.attn_every
    assert gs == 8 and cfg.num_layers % gs == 0, "hybrid assumes Jamba 8-layer groups"
    return cfg.num_layers // gs


# -------------------------------------------------------------------- model

class Model:
    """Architecture-neutral model wrapper (pure functions + explicit params)."""

    def __init__(self, cfg: ArchConfig, *, attn_impl: str = "chunked",
                 attn_chunk: int = 1024, ssd_chunk: int = 256,
                 remat: bool = True, kv_dtype: str = "bfloat16",
                 moe_groups: int = 1, pad_experts_to: int = 0,
                 ssm_state_dtype: str = "float32",
                 chunk_attn_impl: str = "masked"):
        if chunk_attn_impl not in ("masked", "flash"):
            raise ValueError(f"chunk_attn_impl={chunk_attn_impl!r} "
                             "(want 'masked' or 'flash')")
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.chunk_attn_impl = chunk_attn_impl
        self.attn_chunk = attn_chunk
        self.ssd_chunk = ssd_chunk
        self.remat = remat
        self.kv_dtype = kv_dtype
        self.moe_groups = moe_groups
        self.pad_experts_to = pad_experts_to
        self.ssm_state_dtype = ssm_state_dtype
        self.dtype = dtype_of(cfg.param_dtype)

    # ------------------------------------------------------------- params
    def init(self, rng) -> Params:
        cfg, dtype = self.cfg, self.dtype
        k_emb, k_layers, k_head, k_enc, k_x = jax.random.split(rng, 5)
        params: Dict[str, Any] = {
            "embed": L._dense_init(k_emb, (cfg.vocab_size, cfg.d_model),
                                   scale=0.02, dtype=dtype),
            "final_norm": L.init_norm(cfg, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L._dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                              dtype=dtype)
        params["layers"] = self._init_decoder_stack(k_layers)
        if cfg.is_encoder_decoder:
            enc_rngs = jax.random.split(k_enc, cfg.num_encoder_layers)
            params["encoder"] = jax.vmap(
                lambda r: _init_sublayer(cfg, r, "attn", "dense", dtype))(enc_rngs)
            xat_rngs = jax.random.split(k_x, cfg.num_layers)
            params["cross"] = jax.vmap(
                lambda r: {"lnx": L.init_norm(cfg, cfg.d_model, dtype),
                           "xattn": L.init_attention(cfg, r, dtype)})(xat_rngs)
            params["enc_final_norm"] = L.init_norm(cfg, cfg.d_model, dtype)
        return params

    def _init_decoder_stack(self, rng):
        cfg, dtype = self.cfg, self.dtype
        if cfg.family == "hybrid":
            n_groups = _hybrid_group_structure(cfg)

            def init_group(r):
                r_sd, r_sm, r_am = jax.random.split(r, 3)
                pet = self.pad_experts_to
                return {
                    "sd": jax.vmap(lambda rr: _init_sublayer(cfg, rr, "ssm", "dense", dtype))(
                        jax.random.split(r_sd, 4)),
                    "sm": jax.vmap(lambda rr: _init_sublayer(cfg, rr, "ssm", "moe", dtype,
                                                             pet))(
                        jax.random.split(r_sm, 3)),
                    "am": _init_sublayer(cfg, r_am, "attn", "moe", dtype, pet),
                }
            return jax.vmap(init_group)(jax.random.split(rng, n_groups))
        kind = cfg.layer_kind(0)
        ffn_kind = cfg.ffn_kind(0)
        rngs = jax.random.split(rng, cfg.num_layers)
        pet = self.pad_experts_to
        return jax.vmap(lambda r: _init_sublayer(cfg, r, kind, ffn_kind,
                                                 dtype, pet))(rngs)

    # --------------------------------------------------------------- embed
    def _embed_in(self, params, tokens=None, embeds=None):
        if embeds is not None:
            return embeds.astype(self.dtype)
        return jnp.take(params["embed"], tokens, axis=0)

    def _logits(self, params, h):
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        with jax.named_scope("lm_head"):
            return h @ head

    # ------------------------------------------------------------- encoder
    def _encode(self, params, enc_embeds):
        cfg = self.cfg
        x = enc_embeds.astype(self.dtype)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])

        def body(h, p_l):
            h, _, aux = _apply_sublayer_full(
                cfg, p_l, h, positions, "attn", "dense", causal=False,
                want_cache=False, attn_impl=self.attn_impl,
                attn_chunk=self.attn_chunk, ssd_chunk=self.ssd_chunk)
            return h, aux
        fn = jax.checkpoint(body) if self.remat else body
        x, _ = lax.scan(fn, x, params["encoder"])
        return L.apply_norm(cfg, params["enc_final_norm"], x)

    def _cross_kv(self, params, enc_out):
        """Precompute decoder cross-attention K/V from encoder output."""
        cfg = self.cfg

        def per_layer(p_x):
            B, T, _ = enc_out.shape
            k = (enc_out @ p_x["xattn"]["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.hd)
            v = (enc_out @ p_x["xattn"]["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.hd)
            return {"xk": k.astype(dtype_of(self.kv_dtype)),
                    "xv": v.astype(dtype_of(self.kv_dtype))}
        return lax.map(per_layer, params["cross"])

    def _apply_cross(self, params_x, x, xk, xv):
        """Cross-attention sublayer for one decoder layer.  x: (B,S,D)."""
        cfg = self.cfg
        h = L.apply_norm(cfg, params_x["lnx"], x)
        B, S, _ = h.shape
        q = (h @ params_x["xattn"]["wq"]).reshape(B, S, cfg.num_heads, cfg.hd)
        attn = L.full_attention(cfg, q, xk.astype(self.dtype),
                                xv.astype(self.dtype), causal=False)
        return x + attn.reshape(B, S, -1) @ params_x["xattn"]["wo"]

    # ------------------------------------------------------------ training
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """batch: tokens (B,S) | embeds (B,S,D) [+ enc_embeds], targets (B,S)."""
        cfg = self.cfg
        x = self._embed_in(params, batch.get("tokens"), batch.get("embeds"))
        x = shard_hint(x, "batch", None, None)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        enc_ctx = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["enc_embeds"])
            enc_ctx = self._cross_kv(params, enc_out)

        x, aux, _ = self._run_stack_full(params, x, positions, want_cache=False,
                                         enc_ctx=enc_ctx)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x).astype(jnp.float32)

        targets = batch["targets"]
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ce = (logz - tgt_logit).mean()
        zloss = ZLOSS_COEF * (logz ** 2).mean()
        total = ce + zloss + MOE_AUX_COEF * aux
        return total, {"ce": ce, "zloss": zloss, "moe_aux": aux}

    # ----------------------------------------------------- full-seq stacks
    def _run_stack_full(self, params, x, positions, *, want_cache: bool,
                        enc_ctx=None):
        """Run the decoder stack; returns (x, moe_aux, caches-or-None)."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            return self._run_hybrid_full(params, x, positions, want_cache)

        kind, ffn_kind = cfg.layer_kind(0), cfg.ffn_kind(0)

        if cfg.is_encoder_decoder:
            def body(h, inp):
                p_l, p_x, xk, xv = inp
                h, cache, _ = _apply_sublayer_full(
                    cfg, p_l, h, positions, kind, "none", causal=True,
                    want_cache=want_cache, attn_impl=self.attn_impl,
                    attn_chunk=self.attn_chunk, ssd_chunk=self.ssd_chunk)
                h = self._apply_cross(p_x, h, xk, xv)
                h, aux = _apply_ffn_part(cfg, p_l, h, ffn_kind,
                                         self.moe_groups)
                return h, (cache, aux)
            fn = jax.checkpoint(body) if self.remat else body
            x, (caches, auxs) = lax.scan(
                fn, x, (params["layers"], params["cross"],
                        enc_ctx["xk"], enc_ctx["xv"]))
            return x, auxs.sum(), (caches if want_cache else None)

        def body(h, p_l):
            h, cache, aux = _apply_sublayer_full(
                cfg, p_l, h, positions, kind, ffn_kind, causal=True,
                want_cache=want_cache, attn_impl=self.attn_impl,
                attn_chunk=self.attn_chunk, ssd_chunk=self.ssd_chunk,
                moe_groups=self.moe_groups)
            return h, (cache, aux)
        fn = jax.checkpoint(body) if self.remat else body
        x, (caches, auxs) = lax.scan(fn, x, params["layers"])
        return x, auxs.sum(), (caches if want_cache else None)

    def _run_hybrid_full(self, params, x, positions, want_cache: bool):
        cfg = self.cfg

        def group_body(h, p_g):
            caches = {"k": None, "v": None, "conv": [], "ssm": []}
            aux_total = jnp.zeros((), jnp.float32)
            for stack, idx in _HYBRID_ORDER:
                if stack == "am":
                    p_sub = p_g["am"]
                    kind, fk = "attn", "moe"
                else:
                    p_sub = jax.tree.map(lambda a: a[idx], p_g[stack])
                    kind, fk = "ssm", ("dense" if stack == "sd" else "moe")
                h, cache, aux = _apply_sublayer_full(
                    cfg, p_sub, h, positions, kind, fk, causal=True,
                    want_cache=want_cache, attn_impl=self.attn_impl,
                    attn_chunk=self.attn_chunk, ssd_chunk=self.ssd_chunk,
                    moe_groups=self.moe_groups)
                aux_total = aux_total + aux
                if want_cache and cache is not None:
                    if kind == "attn":
                        caches["k"], caches["v"] = cache["k"], cache["v"]
                    else:
                        caches["conv"].append(cache["conv"])
                        caches["ssm"].append(cache["ssm"])
            if want_cache:
                out_cache = {"k": caches["k"], "v": caches["v"],
                             "conv": jnp.stack(caches["conv"]),
                             "ssm": jnp.stack(caches["ssm"])}
            else:
                out_cache = jnp.zeros((), jnp.float32)   # dummy, uniform pytree
            return h, (out_cache, aux_total)

        fn = jax.checkpoint(group_body) if self.remat else group_body
        x, (caches, auxs) = lax.scan(fn, x, params["layers"])
        return x, auxs.sum(), (caches if want_cache else None)

    # ------------------------------------------------------------- prefill
    def prefill(self, params, batch):
        """Process the prompt; return (last_token_logits, cache).

        batch: tokens (B,S) or embeds (B,S,D); enc-dec additionally
        enc_embeds (B,T,D) with a 1-token decoder start.
        """
        cfg = self.cfg
        x = self._embed_in(params, batch.get("tokens"), batch.get("embeds"))
        x = shard_hint(x, "batch", None, None)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        enc_ctx = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["enc_embeds"])
            enc_ctx = self._cross_kv(params, enc_out)

        x, _aux, caches = self._run_stack_full(params, x, positions,
                                               want_cache=True, enc_ctx=enc_ctx)
        x = L.apply_norm(cfg, params["final_norm"], x)
        # right-padded prompts select their true last token via last_index
        last = batch.get("last_index")
        if last is not None:
            x_last = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32)
                                         .repeat(x.shape[-1], -1), axis=1)[:, 0]
        else:
            x_last = x[:, -1, :]
        logits = self._logits(params, x_last)
        cache = self._pack_cache(caches, enc_ctx, batch_size=B, cur_len=S)
        return logits.astype(jnp.float32), cache

    def _pack_cache(self, caches, enc_ctx, batch_size: int, cur_len: int):
        kvd = dtype_of(self.kv_dtype)
        cache: Dict[str, Any] = {
            "lengths": jnp.full((batch_size,), cur_len, jnp.int32)}
        if self.cfg.family == "ssm":
            cache["conv"] = caches["conv"]
            cache["ssm"] = caches["ssm"]
        elif self.cfg.family == "hybrid":
            cache["k"] = caches["k"].astype(kvd)
            cache["v"] = caches["v"].astype(kvd)
            cache["conv"] = caches["conv"]
            cache["ssm"] = caches["ssm"]
        else:
            cache["k"] = caches["k"].astype(kvd)
            cache["v"] = caches["v"].astype(kvd)
        if enc_ctx is not None:
            cache["xk"], cache["xv"] = enc_ctx["xk"], enc_ctx["xv"]
        return cache

    # -------------------------------------------------------------- decode
    def decode_step(self, params, cache, tokens):
        """One decode iteration.  tokens: (B,1) int32.  Returns (logits, cache).

        ``cache["lengths"]`` (B,) counts valid tokens; new KV is written at
        index lengths (caches must be allocated with Smax > lengths).
        """
        cfg = self.cfg
        lengths = cache["lengths"]
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)

        if cfg.family == "ssm":
            def body(h, inp):
                p_l, conv, ssm = inp
                h, new_state = _apply_sublayer_decode(
                    cfg, p_l, h, lengths, "ssm", cfg.ffn_kind(0),
                    {"conv": conv, "ssm": ssm})
                return h, (new_state["conv"], new_state["ssm"])
            x, (conv, ssm) = lax.scan(body, x, (params["layers"],
                                                cache["conv"], cache["ssm"]))
            new_cache = {**cache, "conv": conv, "ssm": ssm,
                         "lengths": lengths + 1}
        elif cfg.family == "hybrid":
            x, new_cache = self._decode_hybrid(params, cache, x, lengths)
        elif cfg.is_encoder_decoder:
            kind, ffn_kind = "attn", cfg.ffn_kind(0)

            def body(h, inp):
                p_l, p_x, k_c, v_c, xk, xv = inp
                h1 = L.apply_norm(cfg, p_l["ln1"], h)
                out, k_c, v_c = _attn_decode(cfg, p_l["attn"], h1, k_c, v_c, lengths)
                h = h + out
                h = self._apply_cross(p_x, h, xk, xv)
                h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind)
                return h, (k_c, v_c)
            x, (k, v) = lax.scan(body, x, (params["layers"], params["cross"],
                                           cache["k"], cache["v"],
                                           cache["xk"], cache["xv"]))
            new_cache = {**cache, "k": k, "v": v, "lengths": lengths + 1}
        else:
            kind, ffn_kind = cfg.layer_kind(0), cfg.ffn_kind(0)

            def body(h, inp):
                p_l, k_c, v_c = inp
                h, nc = _apply_sublayer_decode(cfg, p_l, h, lengths, kind,
                                               ffn_kind, {"k": k_c, "v": v_c})
                return h, (nc["k"], nc["v"])
            x, (k, v) = lax.scan(body, x, (params["layers"],
                                           cache["k"], cache["v"]))
            new_cache = {**cache, "k": k, "v": v, "lengths": lengths + 1}

        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x[:, -1, :])
        return logits.astype(jnp.float32), new_cache

    def decode_step_sampled(self, params, cache, tokens, active, new_gen,
                            new_ctx, true_len, rids, base_key, *,
                            greedy_sampling=True,
                            temp: float = 1.0, top_k: int = 0,
                            eos_token: int = 1, max_new_tokens: int = 128,
                            max_seq_len: int = 256):
        """Fused decode iteration: decode + sample + terminate, one dispatch.

        Wraps :meth:`decode_step` and moves sampling (greedy or
        temperature/top-k) and EOS/length termination *inside* the jitted
        step, so the engine syncs one small ``(tokens, reasons)`` pair per
        iteration instead of one ``int(jnp.argmax(...))`` per slot.

        ``rids`` (B,) int32 + ``base_key`` derive each lane's sampling key
        via :func:`sampler.token_keys` — the token being sampled has
        generation index ``new_gen - 1``, so the stream per (request,
        index) is batch-composition- and speculation-independent.

        ``active`` (B,) bool masks slots with no live request: their cache
        ``lengths`` do not advance and their reason is forced to 0.
        Returns ``(sampled (B,) int32, reason (B,) int32, new_cache)``.
        """
        from repro.serving.sampler import sample_and_reason, token_keys
        logits, cache = self.decode_step(params, cache, tokens)
        lengths = cache["lengths"]
        cache = {**cache, "lengths": jnp.where(active, lengths, lengths - 1)}
        keys = None if greedy_sampling else token_keys(
            base_key, rids, new_gen - 1)
        tok, reason = sample_and_reason(
            logits, keys, greedy_sampling=greedy_sampling, temp=temp,
            top_k=top_k, eos_token=eos_token, max_new_tokens=max_new_tokens,
            max_seq_len=max_seq_len, new_gen=new_gen, new_ctx=new_ctx,
            true_len=true_len)
        reason = jnp.where(active, reason, 0)
        return tok, reason, cache

    # -------------------------------------------------- speculative decode
    def supports_spec_decode(self) -> bool:
        """Verify-k decode shares chunked prefill's requirements: an
        attention-family decoder-only stack, where scoring k+1 positions
        against cached KV is exactly a (tiny) prefill chunk."""
        return self.supports_chunked_prefill()

    def decode_verify(self, params, cache, tokens, *,
                      interpret: bool = False):
        """Score K1 = k+1 decode positions per lane in one dispatch.

        ``tokens``: (B, K1) int32 — column 0 is the lane's previous sampled
        token, columns 1..k its draft tokens.  KV for ``tokens[:, i]`` is
        written at index ``lengths + i``; attention is the same masked
        chunk attention as resumable prefill (causal over ``q_pos =
        lengths + i``), so position i sees exactly the context the
        sequential path would have.  Returns ``(logits (B, K1, V) f32,
        new_cache)`` with ``lengths`` unchanged — the caller commits
        accepted positions by advancing ``lengths``; rejected positions'
        KV stays past the watermark where nothing ever attends to it (and
        the next dispatch overwrites it).
        """
        cfg = self.cfg
        if not self.supports_spec_decode():
            raise ValueError(f"verify-k decode unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        lengths = cache["lengths"]
        B, K1 = tokens.shape
        Smax = cache["k"].shape[2]
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        q_pos = lengths[:, None] + jnp.arange(K1)[None, :]    # (B, K1)
        kv_pos = jnp.broadcast_to(jnp.arange(Smax)[None, :], (B, Smax))
        rows = jnp.arange(B)[:, None]
        ffn_kind = cfg.ffn_kind(0)

        def body(h, inp):
            p_l, k_l, v_l = inp                   # (B, Smax, KVH, hd)
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_l = k_l.at[rows, q_pos].set(k.astype(k_l.dtype))
                v_l = v_l.at[rows, q_pos].set(v.astype(v_l.dtype))
                attn = self._chunk_attn(q, k_l, v_l, q_pos, kv_pos, lengths,
                                        interpret)
                h = h + attn.reshape(B, K1, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, (k_l, v_l)

        x, (k_new, v_new) = lax.scan(body, x, (params["layers"],
                                               cache["k"], cache["v"]))
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x)                      # (B, K1, V)
        return logits.astype(jnp.float32), {**cache, "k": k_new, "v": v_new}

    def decode_verify_sampled(self, params, cache, tokens, n_drafts, active,
                              base_gen, base_ctx, true_len, rids, base_key,
                              *, interpret: bool = False,
                              greedy_sampling=True, temp: float = 1.0,
                              top_k: int = 0, eos_token: int = 1,
                              max_new_tokens: int = 128,
                              max_seq_len: int = 256):
        """Fused verify-k iteration on the dense backend: score k+1
        positions, sample each with its own per-token key, accept the
        longest exact-match draft prefix, and resolve termination — one
        dispatch, one host sync.

        ``base_gen``/``base_ctx``: (B,) generated count / context length
        *before* this dispatch.  Accepted lanes advance ``lengths`` by
        ``n_emit`` (the last emitted token's KV stays unwritten — the next
        dispatch feeds it, same invariant as plain decode); inactive or
        fully-rejected garbage stays past the watermark.  Returns
        ``(samples (B, K1), n_emit (B,), reason (B,), new_cache)``.
        """
        from repro.serving.sampler import token_keys, verify_and_reason
        logits, cache = self.decode_verify(params, cache, tokens,
                                           interpret=interpret)
        B, K1 = tokens.shape
        keys = None
        if not greedy_sampling:
            rr = jnp.repeat(jnp.asarray(rids, jnp.int32), K1)
            ii = (jnp.asarray(base_gen, jnp.int32)[:, None]
                  + jnp.arange(K1, dtype=jnp.int32)[None, :]).reshape(-1)
            keys = token_keys(base_key, rr, ii).reshape(B, K1, -1)
        s, n_emit, reason = verify_and_reason(
            logits, tokens, jnp.asarray(n_drafts, jnp.int32), keys, active,
            greedy_sampling=greedy_sampling, temp=temp, top_k=top_k,
            eos_token=eos_token, max_new_tokens=max_new_tokens,
            max_seq_len=max_seq_len, base_gen=base_gen, base_ctx=base_ctx,
            true_len=true_len)
        cache = {**cache, "lengths": cache["lengths"] + n_emit}
        return s, n_emit, reason, cache

    # ------------------------------------------------------ chunked prefill
    def supports_chunked_prefill(self) -> bool:
        """Chunked (resumable) prefill covers attention-family decoder-only
        stacks: an attention chunk resumes from cached prefix KV exactly,
        while SSM/hybrid recurrent state would need a cross-chunk state
        handoff and enc-dec a static cross cache — both fall back to
        monolithic prefill."""
        cfg = self.cfg
        return (cfg.family not in ("ssm", "hybrid")
                and not cfg.is_encoder_decoder)

    def _chunk_attn(self, q, k_all, v_all, q_pos, kv_pos, start,
                    interpret: bool):
        """Attention for one (possibly packed) prefill chunk.

        ``q``: (B, C, H, hd); ``k_all``/``v_all``: (B, Smax, KVH, hd);
        ``q_pos``/``kv_pos``: (B, C)/(B, Smax); ``start``: (B,) int32.
        ``masked`` materializes the C x Smax score matrix with the causal
        position mask (the bit-identity reference); ``flash`` routes
        through the Pallas ``flash_prefill_prefix`` kernel, which reads
        the stripe blockwise with online softmax — same math, no dense
        score matrix, so it survives long contexts.  ``interpret`` runs
        the kernel in Pallas interpret mode (the caller decides it from
        its device's platform).
        """
        cfg = self.cfg
        if self.chunk_attn_impl == "flash":
            from repro.kernels.flash_prefill.flash_prefill import (
                flash_prefill_prefix)
            out = flash_prefill_prefix(
                q.transpose(0, 2, 1, 3),            # (B, H, C, hd)
                k_all.astype(q.dtype).transpose(0, 2, 1, 3),
                v_all.astype(q.dtype).transpose(0, 2, 1, 3),
                start, interpret=interpret)
            return out.transpose(0, 2, 1, 3)        # (B, C, H, hd)
        return L.full_attention(cfg, q, k_all, v_all, causal=True,
                                q_positions=q_pos, kv_positions=kv_pos)

    def prefill_chunk(self, params, k_stripe, v_stripe, tokens, start,
                      chunk_len, *, interpret: bool = False):
        """One resumable prefill chunk over a dense per-request KV stripe.

        ``k_stripe``/``v_stripe``: (L, Smax, KVH, hd) — the request's slot
        stripes with tokens ``[0, start)`` already materialized by earlier
        chunks; ``tokens``: (1, C) int32, right-padded past ``chunk_len``;
        ``start``/``chunk_len`` are dynamic scalars.  Writes the chunk's KV
        at absolute positions ``[start, start+C)`` (out-of-range padded
        rows are dropped by JAX's scatter OOB semantics) and attends each
        chunk query at absolute position ``start+i`` over stripe keys
        ``j <= start+i`` — unwritten stripe positions are masked, so stale
        lane contents never leak into the output.

        Returns ``(last_logits (1, V) f32, new_k, new_v)`` where
        ``last_logits`` is taken at local index ``chunk_len - 1`` (the
        prompt's next-token logits when this is the final chunk).
        """
        cfg = self.cfg
        if not self.supports_chunked_prefill():
            raise ValueError(f"chunked prefill unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        C = tokens.shape[1]
        Smax = k_stripe.shape[1]
        x = self._embed_in(params, tokens)                    # (1, C, D)
        x = shard_hint(x, "batch", None, None)
        q_pos = (start + jnp.arange(C))[None, :]              # (1, C)
        kv_pos = jnp.arange(Smax)[None, :]                    # (1, Smax)
        write_idx = start + jnp.arange(C)                     # (C,)
        start_vec = jnp.reshape(jnp.asarray(start, jnp.int32), (1,))
        ffn_kind = cfg.ffn_kind(0)

        def body(h, inp):
            p_l, k_l, v_l = inp                               # (Smax, KVH, hd)
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_l = k_l.at[write_idx].set(k[0].astype(k_l.dtype))
                v_l = v_l.at[write_idx].set(v[0].astype(v_l.dtype))
                attn = self._chunk_attn(q, k_l[None], v_l[None], q_pos,
                                        kv_pos, start_vec, interpret)
                h = h + attn.reshape(1, C, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, (k_l, v_l)

        x, (k_new, v_new) = lax.scan(body, x,
                                     (params["layers"], k_stripe, v_stripe))
        x = L.apply_norm(cfg, params["final_norm"], x)
        last = jnp.clip(chunk_len - 1, 0, C - 1)
        x_last = jax.lax.dynamic_index_in_dim(x, last, axis=1,
                                              keepdims=False)
        logits = self._logits(params, x_last)
        return logits.astype(jnp.float32), k_new, v_new

    def _scan_paged(self, params, kv, x, layer_fn):
        """Layer scan over a paged pool carried in the scan *carry*:
        ``layer_fn(h, p_l, k_all, v_all, i) -> (h, k_all, v_all)`` scatters
        layer ``i``'s new KV into the whole (L, pages, ...) pool and reads
        its pages back by gather.  XLA then updates the one (donated) pool
        buffer in place; a pool passed as scan xs/ys would need a second
        whole-pool buffer for the stacked outputs."""
        def step(carry, inp):
            h, k_all, v_all = carry
            p_l, i = inp
            return layer_fn(h, p_l, k_all, v_all, i), None

        (x, k_new, v_new), _ = lax.scan(
            step, (x, kv["k"], kv["v"]),
            (params["layers"], jnp.arange(self.cfg.num_layers)))
        return x, {"k": k_new, "v": v_new}

    def paged_prefill_chunk(self, params, kv, tokens, block_tables,
                            write_page, write_off, start, chunk_len, *,
                            interpret: bool = False):
        """Paged twin of :meth:`prefill_chunk`: the chunk's KV lands
        directly in the page pool (device-side, mid-page chunk boundaries
        included) and attention gathers the request's pages in logical
        order — the same masked ops as the dense stripe path, so greedy
        outputs stay bit-identical across backends.

        ``kv``: {"k","v"} (L, num_pages, page, KVH, hd); ``block_tables``:
        (1, max_pages) int32 with unused entries pointing at the scratch
        page; ``write_page``/``write_off``: (C,) physical destination of
        each chunk token (scratch for padded rows).
        """
        cfg = self.cfg
        if not self.supports_chunked_prefill():
            raise ValueError(f"chunked prefill unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        C = tokens.shape[1]
        page = kv["k"].shape[2]
        n_pages = block_tables.shape[1]
        Smax = n_pages * page
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        q_pos = (start + jnp.arange(C))[None, :]
        kv_pos = jnp.arange(Smax)[None, :]
        start_vec = jnp.reshape(jnp.asarray(start, jnp.int32), (1,))
        ffn_kind = cfg.ffn_kind(0)

        def body(h, p_l, k_all, v_all, i):
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_all = k_all.at[i, write_page, write_off].set(
                    k[0].astype(k_all.dtype))
                v_all = v_all.at[i, write_page, write_off].set(
                    v[0].astype(v_all.dtype))
                kg = k_all[i, block_tables[0]].reshape(1, Smax,
                                                       *k_all.shape[3:])
                vg = v_all[i, block_tables[0]].reshape(1, Smax,
                                                       *v_all.shape[3:])
                attn = self._chunk_attn(q, kg, vg, q_pos, kv_pos, start_vec,
                                        interpret)
                h = h + attn.reshape(1, C, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, k_all, v_all

        x, kv = self._scan_paged(params, kv, x, body)
        x = L.apply_norm(cfg, params["final_norm"], x)
        last = jnp.clip(chunk_len - 1, 0, C - 1)
        x_last = jax.lax.dynamic_index_in_dim(x, last, axis=1,
                                              keepdims=False)
        logits = self._logits(params, x_last)
        return logits.astype(jnp.float32), kv

    # ------------------------------------------------------- packed prefill
    def supports_prefill_pack(self) -> bool:
        """Packed prefill batches several requests' chunks through one
        dispatch.  Attention/norm/dense-FFN treat batch rows independently,
        so packing preserves per-segment outputs bit-for-bit — but MoE
        expert capacity is a function of the *total* token count
        (``capacity_factor * T * K / E``), so co-batched segments would
        change each other's drop behavior.  Packing therefore covers
        dense-FFN attention stacks only.
        """
        return self.supports_chunked_prefill() and self.cfg.ffn_kind(0) != "moe"

    def prefill_pack(self, params, k_stripes, v_stripes, tokens, start,
                     chunk_len, *, interpret: bool = False):
        """N prefill chunks from distinct requests in one dispatch.

        Batched twin of :meth:`prefill_chunk` — segment ``i`` occupies
        batch row ``i``, so its computation is the same masked attention
        over its own (Smax) stripe as the unpacked path and greedy outputs
        stay bit-identical packed-vs-unpacked.

        ``k_stripes``/``v_stripes``: (L, N, Smax, KVH, hd); ``tokens``:
        (N, C) int32 right-padded; ``start``/``chunk_len``: (N,) int32.
        Dummy rows (pack padding) carry ``chunk_len = 0`` and whatever
        stripe the caller gathered; their outputs are garbage the caller
        discards.  Returns ``(last_logits (N, V) f32, new_k, new_v)``.
        """
        cfg = self.cfg
        if not self.supports_prefill_pack():
            raise ValueError(
                f"packed prefill unsupported for family={cfg.family} "
                f"ffn={cfg.ffn_kind(0)} enc_dec={cfg.is_encoder_decoder}")
        N, C = tokens.shape
        Smax = k_stripes.shape[2]
        x = self._embed_in(params, tokens)                    # (N, C, D)
        x = shard_hint(x, "batch", None, None)
        start = jnp.asarray(start, jnp.int32)
        q_pos = start[:, None] + jnp.arange(C)[None, :]       # (N, C)
        kv_pos = jnp.broadcast_to(jnp.arange(Smax)[None, :], (N, Smax))
        rows = jnp.arange(N)[:, None]                         # (N, 1)
        write_idx = q_pos                                     # (N, C)
        ffn_kind = cfg.ffn_kind(0)

        def body(h, inp):
            p_l, k_l, v_l = inp                               # (N, Smax, KVH, hd)
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_l = k_l.at[rows, write_idx].set(k.astype(k_l.dtype))
                v_l = v_l.at[rows, write_idx].set(v.astype(v_l.dtype))
                attn = self._chunk_attn(q, k_l, v_l, q_pos, kv_pos, start,
                                        interpret)
                h = h + attn.reshape(N, C, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, (k_l, v_l)

        x, (k_new, v_new) = lax.scan(body, x,
                                     (params["layers"], k_stripes, v_stripes))
        x = L.apply_norm(cfg, params["final_norm"], x)
        last = jnp.clip(chunk_len - 1, 0, C - 1)              # (N,)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = self._logits(params, x_last)                 # (N, V)
        return logits.astype(jnp.float32), k_new, v_new

    def paged_prefill_pack(self, params, kv, tokens, block_tables,
                           write_page, write_off, start, chunk_len, *,
                           interpret: bool = False):
        """Paged twin of :meth:`prefill_pack`: N chunks' KV lands in the
        shared page pool in one dispatch.

        ``kv``: {"k","v"} (L, num_pages, page, KVH, hd); ``tokens``:
        (N, C); ``block_tables``: (N, max_pages) with unused entries at
        the scratch page; ``write_page``/``write_off``: (N, C) physical
        destination per token (scratch for padded rows and dummy
        segments); ``start``/``chunk_len``: (N,) int32.
        """
        cfg = self.cfg
        if not self.supports_prefill_pack():
            raise ValueError(
                f"packed prefill unsupported for family={cfg.family} "
                f"ffn={cfg.ffn_kind(0)} enc_dec={cfg.is_encoder_decoder}")
        N, C = tokens.shape
        page = kv["k"].shape[2]
        n_pages = block_tables.shape[1]
        Smax = n_pages * page
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        start = jnp.asarray(start, jnp.int32)
        q_pos = start[:, None] + jnp.arange(C)[None, :]
        kv_pos = jnp.broadcast_to(jnp.arange(Smax)[None, :], (N, Smax))
        ffn_kind = cfg.ffn_kind(0)

        def body(h, p_l, k_all, v_all, i):
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_all = k_all.at[i, write_page, write_off].set(
                    k.astype(k_all.dtype))
                v_all = v_all.at[i, write_page, write_off].set(
                    v.astype(v_all.dtype))
                kg = k_all[i, block_tables].reshape(N, Smax, *k_all.shape[3:])
                vg = v_all[i, block_tables].reshape(N, Smax, *v_all.shape[3:])
                attn = self._chunk_attn(q, kg, vg, q_pos, kv_pos, start,
                                        interpret)
                h = h + attn.reshape(N, C, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, k_all, v_all

        x, kv = self._scan_paged(params, kv, x, body)
        x = L.apply_norm(cfg, params["final_norm"], x)
        last = jnp.clip(chunk_len - 1, 0, C - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = self._logits(params, x_last)
        return logits.astype(jnp.float32), kv

    # ------------------------------------------------------- paged decode
    def supports_paged(self) -> bool:
        """Paged KV decode covers attention-family decoder-only stacks
        (SSM/hybrid state is constant-size — paging buys nothing — and
        enc-dec carries a static cross cache)."""
        cfg = self.cfg
        return (cfg.family not in ("ssm", "hybrid")
                and not cfg.is_encoder_decoder)

    def paged_decode_step(self, params, kv, tokens, block_tables, lengths,
                          write_page, write_off, *, attn_impl: str = "gather",
                          interpret: bool = False):
        """One decode iteration over a paged KV pool (vLLM-style block KV).

        ``kv``: {"k","v"} of shape (L, num_pages, page, KVH, hd);
        ``tokens`` (B, 1) int32; ``block_tables`` (B, max_pages) int32 with
        unused entries pointing at a sacrificial page; ``lengths`` (B,) =
        tokens already written, so the fed token's KV lands at logical
        position ``lengths`` = physical ``(write_page, write_off)``.

        ``attn_impl="gather"`` materializes the pages in logical order and
        reuses :func:`layers.decode_attention` — bit-identical to the dense
        slotted path (same ops on the same values), which is what the
        dense-vs-paged greedy invariant tests pin.  ``"kernel"`` routes
        through the Pallas paged-attention kernel (no gather — the block
        table drives scalar-prefetch DMA), numerically equal within
        online-softmax reassociation.

        Returns ``(logits (B, V) f32, new_kv)``.
        """
        cfg = self.cfg
        if not self.supports_paged():
            raise ValueError(f"paged decode unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        B = tokens.shape[0]
        page = kv["k"].shape[2]
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        positions = lengths[:, None]
        ffn_kind = cfg.ffn_kind(0)

        def body(h, p_l, k_all, v_all, i):
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, positions)
                k_all = k_all.at[i, write_page, write_off].set(
                    k[:, 0].astype(k_all.dtype))
                v_all = v_all.at[i, write_page, write_off].set(
                    v[:, 0].astype(v_all.dtype))
                if attn_impl == "kernel":
                    from repro.kernels.paged_attention.paged_attention \
                        import paged_attention
                    attn = paged_attention(q[:, 0], k_all, v_all,
                                           block_tables, lengths + 1,
                                           layer=i, interpret=interpret)
                else:
                    n_pages = block_tables.shape[1]
                    kg = k_all[i, block_tables].reshape(
                        B, n_pages * page, *k_all.shape[3:])
                    vg = v_all[i, block_tables].reshape(
                        B, n_pages * page, *v_all.shape[3:])
                    attn = L.decode_attention(cfg, q[:, 0], kg, vg,
                                              lengths + 1)
                h = h + (attn.reshape(B, -1) @ p_l["attn"]["wo"])[:, None, :]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind)
            return h, k_all, v_all

        x, kv = self._scan_paged(params, kv, x, body)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x[:, -1, :])
        return logits.astype(jnp.float32), kv

    def paged_decode_step_sampled(self, params, kv, tokens, block_tables,
                                  lengths, write_page, write_off, active,
                                  new_gen, new_ctx, true_len, rids, base_key,
                                  *, attn_impl: str = "gather",
                                  interpret: bool = False,
                                  greedy_sampling=True, temp: float = 1.0,
                                  top_k: int = 0, eos_token: int = 1,
                                  max_new_tokens: int = 128,
                                  max_seq_len: int = 256):
        """Paged twin of :meth:`decode_step_sampled`: one fused dispatch
        returning ``(sampled, reason, new_kv)``.  Per-lane sampling keys
        derive from ``(rids, new_gen - 1)`` exactly as on the dense path."""
        from repro.serving.sampler import sample_and_reason, token_keys
        logits, kv = self.paged_decode_step(
            params, kv, tokens, block_tables, lengths, write_page, write_off,
            attn_impl=attn_impl, interpret=interpret)
        keys = None if greedy_sampling else token_keys(
            base_key, rids, new_gen - 1)
        tok, reason = sample_and_reason(
            logits, keys, greedy_sampling=greedy_sampling, temp=temp,
            top_k=top_k, eos_token=eos_token, max_new_tokens=max_new_tokens,
            max_seq_len=max_seq_len, new_gen=new_gen, new_ctx=new_ctx,
            true_len=true_len)
        reason = jnp.where(active, reason, 0)
        return tok, reason, kv

    def paged_decode_verify(self, params, kv, tokens, block_tables, lengths,
                            write_page, write_off, *,
                            interpret: bool = False):
        """Paged twin of :meth:`decode_verify`: K1 positions per lane land
        in the page pool at host-computed ``(write_page, write_off)``
        destinations (real tail pages in place; positions past the last
        allocated page on the lane's private scratch page, which the
        backend promotes into the page table only on accept).

        ``block_tables``: (B, max_pages + 1) with the lane scratch page
        appended right after the lane's real pages, so a scratch-resident
        position's gather index equals its logical position; stale scratch
        offsets sit past every query position and are causally masked.
        Attention is always the gather form — the Pallas paged kernel is
        single-query — which keeps the verify math bit-identical to the
        dense chunk attention.  Returns ``(logits (B, K1, V) f32, new_kv)``.
        """
        cfg = self.cfg
        if not (self.supports_spec_decode() and self.supports_paged()):
            raise ValueError(f"paged verify-k unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        B, K1 = tokens.shape
        page = kv["k"].shape[2]
        n_pages = block_tables.shape[1]
        Smax = n_pages * page
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        q_pos = lengths[:, None] + jnp.arange(K1)[None, :]
        kv_pos = jnp.broadcast_to(jnp.arange(Smax)[None, :], (B, Smax))
        ffn_kind = cfg.ffn_kind(0)

        def body(h, p_l, k_all, v_all, i):
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_all = k_all.at[i, write_page, write_off].set(
                    k.astype(k_all.dtype))
                v_all = v_all.at[i, write_page, write_off].set(
                    v.astype(v_all.dtype))
                kg = k_all[i, block_tables].reshape(B, Smax, *k_all.shape[3:])
                vg = v_all[i, block_tables].reshape(B, Smax, *v_all.shape[3:])
                attn = self._chunk_attn(q, kg, vg, q_pos, kv_pos, lengths,
                                        interpret)
                h = h + attn.reshape(B, K1, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, k_all, v_all

        x, kv = self._scan_paged(params, kv, x, body)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = self._logits(params, x)
        return logits.astype(jnp.float32), kv

    def paged_decode_verify_sampled(self, params, kv, tokens, block_tables,
                                    lengths, write_page, write_off, n_drafts,
                                    active, base_gen, base_ctx, true_len,
                                    rids, base_key, *,
                                    interpret: bool = False,
                                    greedy_sampling=True,
                                    temp: float = 1.0, top_k: int = 0,
                                    eos_token: int = 1,
                                    max_new_tokens: int = 128,
                                    max_seq_len: int = 256):
        """Fused paged verify-k iteration: score + sample + accept +
        terminate, one dispatch.  Length bookkeeping is host-side on the
        paged backend, so this returns ``(samples, n_emit, reason,
        new_kv)`` and the backend commits page-table state after the sync
        (rollback = simply not advancing the pool length)."""
        from repro.serving.sampler import token_keys, verify_and_reason
        logits, kv = self.paged_decode_verify(
            params, kv, tokens, block_tables, lengths, write_page, write_off,
            interpret=interpret)
        B, K1 = tokens.shape
        keys = None
        if not greedy_sampling:
            rr = jnp.repeat(jnp.asarray(rids, jnp.int32), K1)
            ii = (jnp.asarray(base_gen, jnp.int32)[:, None]
                  + jnp.arange(K1, dtype=jnp.int32)[None, :]).reshape(-1)
            keys = token_keys(base_key, rr, ii).reshape(B, K1, -1)
        s, n_emit, reason = verify_and_reason(
            logits, tokens, jnp.asarray(n_drafts, jnp.int32), keys, active,
            greedy_sampling=greedy_sampling, temp=temp, top_k=top_k,
            eos_token=eos_token, max_new_tokens=max_new_tokens,
            max_seq_len=max_seq_len, base_gen=base_gen, base_ctx=base_ctx,
            true_len=true_len)
        return s, n_emit, reason, kv

    def _decode_hybrid(self, params, cache, x, lengths):
        cfg = self.cfg

        def group_body(h, inp):
            p_g, k_c, v_c, conv_c, ssm_c = inp
            new_conv, new_ssm = [], []
            ssm_i = 0
            for stack, idx in _HYBRID_ORDER:
                if stack == "am":
                    h, nc = _apply_sublayer_decode(cfg, p_g["am"], h, lengths,
                                                   "attn", "moe",
                                                   {"k": k_c, "v": v_c})
                    k_c, v_c = nc["k"], nc["v"]
                else:
                    p_sub = jax.tree.map(lambda a: a[idx], p_g[stack])
                    fk = "dense" if stack == "sd" else "moe"
                    h, nc = _apply_sublayer_decode(
                        cfg, p_sub, h, lengths, "ssm", fk,
                        {"conv": conv_c[ssm_i], "ssm": ssm_c[ssm_i]})
                    new_conv.append(nc["conv"])
                    new_ssm.append(nc["ssm"])
                    ssm_i += 1
            return h, (k_c, v_c, jnp.stack(new_conv), jnp.stack(new_ssm))

        x, (k, v, conv, ssm) = lax.scan(
            group_body, x, (params["layers"], cache["k"], cache["v"],
                            cache["conv"], cache["ssm"]))
        new_cache = {**cache, "k": k, "v": v, "conv": conv, "ssm": ssm,
                     "lengths": lengths + 1}
        return x, new_cache

    # --------------------------------------------------------- cache specs
    def cache_shapes(self, batch: int, max_len: int) -> Dict[str, Any]:
        """Shape/dtype template (as ShapeDtypeStructs) for a decode cache."""
        cfg = self.cfg
        kvd = dtype_of(self.kv_dtype)
        sds = jax.ShapeDtypeStruct
        KVH, hd = cfg.num_kv_heads, cfg.hd
        out: Dict[str, Any] = {"lengths": sds((batch,), jnp.int32)}
        if cfg.family == "ssm":
            n = cfg.num_layers
            out["conv"] = sds((n, batch, cfg.conv_width - 1,
                               cfg.d_inner + 2 * cfg.ssm_state), self.dtype)
            out["ssm"] = sds((n, batch, cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.ssm_state), dtype_of(self.ssm_state_dtype))
        elif cfg.family == "hybrid":
            g = _hybrid_group_structure(cfg)
            out["k"] = sds((g, batch, max_len, KVH, hd), kvd)
            out["v"] = sds((g, batch, max_len, KVH, hd), kvd)
            out["conv"] = sds((g, 7, batch, cfg.conv_width - 1,
                               cfg.d_inner + 2 * cfg.ssm_state), self.dtype)
            out["ssm"] = sds((g, 7, batch, cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.ssm_state), dtype_of(self.ssm_state_dtype))
        else:
            n = cfg.num_layers
            out["k"] = sds((n, batch, max_len, KVH, hd), kvd)
            out["v"] = sds((n, batch, max_len, KVH, hd), kvd)
            if cfg.is_encoder_decoder:
                out["xk"] = sds((n, batch, cfg.cross_kv_len, KVH, hd), kvd)
                out["xv"] = sds((n, batch, cfg.cross_kv_len, KVH, hd), kvd)
        return out

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch, max_len))

    # --------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        sds = jax.ShapeDtypeStruct
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            batch: Dict[str, Any] = {"targets": sds((B, S), jnp.int32)}
            if cfg.input_mode == "embeds" and not cfg.is_encoder_decoder:
                batch["embeds"] = sds((B, S, cfg.d_model), self.dtype)
            else:
                batch["tokens"] = sds((B, S), jnp.int32)
            if cfg.is_encoder_decoder:
                batch["enc_embeds"] = sds((B, S, cfg.d_model), self.dtype)
            return batch
        if shape.kind == "prefill":
            batch = {}
            if cfg.is_encoder_decoder:
                batch["enc_embeds"] = sds((B, S, cfg.d_model), self.dtype)
                batch["tokens"] = sds((B, 1), jnp.int32)
            elif cfg.input_mode == "embeds":
                batch["embeds"] = sds((B, S, cfg.d_model), self.dtype)
            else:
                batch["tokens"] = sds((B, S), jnp.int32)
            return batch
        # decode: one new token against a cache of S tokens (S-1 filled)
        return {"tokens": sds((B, 1), jnp.int32),
                "cache": self.cache_shapes(B, S)}
