"""Model assembly: decoder-only / MoE / SSM / hybrid / encoder-decoder stacks
with scan-over-layers, remat, and train / prefill / decode entry points.
Serving an attention-family decoder-only stack runs one forward,
:meth:`Model.forward_paged`, at every width (decode, verify-k, a prefill
chunk, a pack of chunks) over a KV page pool; a dense stripe cache is a
pool of one page per slot (:func:`stripe_view`).

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


def stripe_view(slots, start, width: int):
    """A dense ``(L, B, Smax, KVH, hd)`` cache as the page pool of
    :meth:`Model.forward_paged`: B pages of Smax, slot ``b`` is page ``b``
    and position ``p`` of its stripe offset ``p``.  For rows on ``slots``
    (N,) whose queries start at ``start`` (N,), returns the page tables
    (N, 1) and the write targets ``(write_page, write_off)`` (N, width).
    An out-of-range slot (a pack's dummy row) reads a clamped stripe and
    its writes are dropped, as are writes past Smax."""
    slots = jnp.asarray(slots, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    write_page = jnp.broadcast_to(slots[:, None], (slots.shape[0], width))
    return slots[:, None], write_page, start[:, None] + jnp.arange(width)


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
        cache = self._pack_cache(caches, enc_ctx, batch_size=B, cur_len=S)
        return self.logits(params, x_last), cache

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
        index lengths (caches must be allocated with Smax > lengths).  An
        attention-family decoder-only stack runs :meth:`forward_paged` over
        its ``(L, B, Smax, ...)`` cache seen as B pages of Smax
        (:func:`stripe_view`).
        """
        cfg = self.cfg
        lengths = cache["lengths"]
        if self.supports_paged():
            slots = jnp.arange(tokens.shape[0])
            h, kv = self.forward_paged(
                params, {"k": cache["k"], "v": cache["v"]}, tokens,
                *stripe_view(slots, lengths, 1), lengths)
            return (self.logits(params, h[:, -1]),
                    {**cache, **kv, "lengths": lengths + 1})
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
        else:                                       # encoder-decoder
            ffn_kind = cfg.ffn_kind(0)

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

        x = L.apply_norm(cfg, params["final_norm"], x)
        return self.logits(params, x[:, -1, :]), new_cache

    def logits(self, params, h):
        """The lm head, in float32, over final-normed hidden states."""
        return self._logits(params, h).astype(jnp.float32)

    def chunk_logits(self, params, h, chunk_len):
        """Logits (N, V) at each row's last real column ``chunk_len - 1``
        of hidden states ``h`` (N, C, D): the next-token logits of a
        prompt whose final chunk this is."""
        last = jnp.clip(chunk_len - 1, 0, h.shape[1] - 1)
        return self.logits(params, jnp.take_along_axis(
            h, last[:, None, None], axis=1)[:, 0])

    # ------------------------------------- the paged attention-family forward
    def supports_paged(self) -> bool:
        """:meth:`forward_paged` (paged KV, chunked prefill, verify-k)
        covers attention-family decoder-only stacks: SSM/hybrid state is
        constant-size and would need a cross-chunk state handoff, and
        enc-dec carries a static cross cache — those families decode
        through :meth:`decode_step` and prefill monolithically."""
        cfg = self.cfg
        return (cfg.family not in ("ssm", "hybrid")
                and not cfg.is_encoder_decoder)

    def supports_prefill_pack(self) -> bool:
        """Packed prefill batches several requests' chunks through one
        dispatch.  Attention/norm/dense-FFN treat batch rows independently,
        so packing preserves per-segment outputs bit-for-bit — but MoE
        expert capacity is a function of the *total* token count
        (``capacity_factor * T * K / E``), so co-batched segments would
        change each other's drop behavior.  Packing therefore covers
        dense-FFN attention stacks only.
        """
        return self.supports_paged() and self.cfg.ffn_kind(0) != "moe"

    def _chunk_attn(self, q, k_all, v_all, q_pos, kv_pos, start,
                    interpret: bool):
        """Attention of C > 1 queries per row over the row's gathered KV.

        ``q``: (N, C, H, hd); ``k_all``/``v_all``: (N, S, KVH, hd);
        ``q_pos``/``kv_pos``: (N, C)/(N, S); ``start``: (N,) int32.
        ``masked`` materializes the C x S score matrix with the causal
        position mask (the bit-identity reference); ``flash`` routes
        through the Pallas ``flash_prefill_prefix`` kernel, which reads
        the keys blockwise with online softmax — same math, no dense
        score matrix, so it survives long contexts.  ``interpret`` runs
        the kernel in Pallas interpret mode (the caller decides it from
        its device's platform).
        """
        cfg = self.cfg
        if self.chunk_attn_impl == "flash":
            from repro.kernels.flash_prefill.flash_prefill import (
                flash_prefill_prefix)
            out = flash_prefill_prefix(
                q.transpose(0, 2, 1, 3),            # (N, H, C, hd)
                k_all.astype(q.dtype).transpose(0, 2, 1, 3),
                v_all.astype(q.dtype).transpose(0, 2, 1, 3),
                start, interpret=interpret)
            return out.transpose(0, 2, 1, 3)        # (N, C, H, hd)
        return L.full_attention(cfg, q, k_all, v_all, causal=True,
                                q_positions=q_pos, kv_positions=kv_pos)

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

    def forward_paged(self, params, kv, tokens, block_tables, write_page,
                      write_off, start, *, attn_impl: str = "gather",
                      interpret: bool = False):
        """The attention-family forward at every serving width: decode
        (C = 1), verify-k (C = k+1), one prefill chunk (N = 1) and a pack
        of N chunks, over a KV page pool.

        ``kv``: {"k","v"} (L, pages, page, KVH, hd); ``tokens``: (N, C)
        int32; ``block_tables``: (N, W) int32, row n's pages in logical
        order (unused entries at a scratch page); ``write_page``/
        ``write_off``: (N, C) physical destination of each token's KV
        (pad columns at a scratch page; out-of-range targets are dropped
        by JAX's scatter); ``start``: (N,) int32.  Row n's queries sit at
        positions ``start[n] + arange(C)`` and see keys up to their own
        position, so unwritten and stale positions never reach an output.
        A dense stripe cache is a pool of pages Smax long
        (:func:`stripe_view`).

        Attention follows the static width: at C = 1 the gathered pages go
        through :func:`layers.decode_attention` (with ``attn_impl=
        "kernel"`` the Pallas paged-attention kernel reads the pool by the
        page table instead); at C > 1 through :meth:`_chunk_attn`.

        Returns the final-normed hidden states (N, C, D) and the new
        ``kv``; the caller applies the lm head only where it needs logits.
        """
        cfg = self.cfg
        if not self.supports_paged():
            raise ValueError(f"the paged forward is unsupported for family="
                             f"{cfg.family} enc_dec={cfg.is_encoder_decoder}")
        N, C = tokens.shape
        S = block_tables.shape[1] * kv["k"].shape[2]
        x = self._embed_in(params, tokens)
        x = shard_hint(x, "batch", None, None)
        start = jnp.asarray(start, jnp.int32)
        q_pos = start[:, None] + jnp.arange(C)[None, :]       # (N, C)
        kv_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (N, S))
        ffn_kind = cfg.ffn_kind(0)

        def body(h, p_l, k_all, v_all, i):
            h1 = L.apply_norm(cfg, p_l["ln1"], h)
            with jax.named_scope("attention"):
                q, k, v = L._project_qkv(cfg, p_l["attn"], h1, q_pos)
                k_all = k_all.at[i, write_page, write_off].set(
                    k.astype(k_all.dtype))
                v_all = v_all.at[i, write_page, write_off].set(
                    v.astype(v_all.dtype))
                if C == 1 and attn_impl == "kernel":
                    from repro.kernels.paged_attention.paged_attention \
                        import paged_attention
                    attn = paged_attention(q[:, 0], k_all, v_all,
                                           block_tables, start + 1,
                                           layer=i, interpret=interpret)
                else:
                    kg = k_all[i, block_tables].reshape(N, S,
                                                        *k_all.shape[3:])
                    vg = v_all[i, block_tables].reshape(N, S,
                                                        *v_all.shape[3:])
                    if C == 1:
                        attn = L.decode_attention(cfg, q[:, 0], kg, vg,
                                                  start + 1)
                    else:
                        attn = self._chunk_attn(q, kg, vg, q_pos, kv_pos,
                                                start, interpret)
                h = h + attn.reshape(N, C, -1) @ p_l["attn"]["wo"]
            h, _ = _apply_ffn_part(cfg, p_l, h, ffn_kind, self.moe_groups)
            return h, k_all, v_all

        x, kv = self._scan_paged(params, kv, x, body)
        return L.apply_norm(cfg, params["final_norm"], x), kv

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
