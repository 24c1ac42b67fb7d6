"""LM assembly: every assigned architecture as a stage program.

An architecture compiles to a list of **stages**; each stage is a
``lax.scan`` over ``repeat`` structurally-identical **groups** of layers
(params stacked on the scan axis — compact HLO even for 61-layer models).
A group is a list of layer descriptors ``(mixer, ffn)``:

    mixer: gqa | mla | mamba | rwkv        ffn: mlp | moe | rwkv_cm | none

  dense (llama/qwen/llava):  1 stage x L  [(gqa, mlp)]
  mixtral:                   1 stage x L  [(gqa, moe)]
  deepseek-v3:               (mla, mlp) x3 dense head, then (mla, moe) x58
  jamba:                     4 periods of "mmmammmm" with MoE on odd slots
  rwkv6:                     1 stage x L  [(rwkv, rwkv_cm)]
  whisper:                   encoder stage (bidir gqa) + decoder stage
                             (causal gqa + cross-attn)

Parameters are nested dicts; a parallel **template** tree carries
(shape, PartitionSpec, dtype) for init / dry-run ShapeDtypeStructs /
``jit`` in_shardings.  Sharding follows Megatron TP on ``model`` (+FSDP
'data' for optimizer state, see repro.train): attention heads and FFN hidden
column/row-split, vocab-parallel embedding + CE via ``shard_map``, MoE
experts sharded on ``model`` with the one-psum replicated-EP dispatch
(repro.models.moe docstring — the Outback decoupling analogy).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import attention as att
from repro.models import mamba as mam
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.common import KeyGen, rms_norm, silu


# --------------------------------------------------------------- templates
@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    spec: P = P()
    dtype: str = "bfloat16"
    scale: float | None = None  # None => 1/sqrt(fan_in)


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _tp(dim: int, tp: int) -> bool:
    return tp > 1 and dim % tp == 0


# ------------------------------------------------------------ layer descs
def make_program(cfg: ModelConfig):
    """-> list of stages; stage = (repeat, [ (mixer, ffn) ... ])."""
    if cfg.family in ("dense", "vlm"):
        return [(cfg.num_layers, [("gqa", "mlp")])]
    if cfg.family == "ssm":
        return [(cfg.num_layers, [("rwkv", "rwkv_cm")])]
    if cfg.family == "moe" and cfg.attn_kind == "mla":
        k = cfg.moe.first_k_dense
        prog = []
        if k:
            prog.append((k, [("mla", "mlp")]))
        prog.append((cfg.num_layers - k, [("mla", "moe")]))
        return prog
    if cfg.family == "moe":
        return [(cfg.num_layers, [("gqa", "moe")])]
    if cfg.family == "hybrid":
        pat = cfg.layer_pattern
        period = len(pat)
        assert cfg.num_layers % period == 0
        group = []
        for i, ch in enumerate(pat):
            mixer = "gqa" if ch == "a" else "mamba"
            ffn = "moe" if (cfg.moe and i % cfg.moe.every_k == 1) else "mlp"
            group.append((mixer, ffn))
        return [(cfg.num_layers // period, group)]
    if cfg.family == "encdec":
        # handled by the encdec wrapper; decoder program:
        return [(cfg.num_layers, [("gqa_cross", "mlp")])]
    raise ValueError(cfg.family)


# ------------------------------------------------------- param templates
def _mixer_template(kind: str, cfg: ModelConfig, tp: int):
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    col = lambda s: P(None, "model") if _tp(s[-1], tp) else P()
    row = lambda s: P("model", None) if _tp(s[0], tp) else P()
    t = {}
    if kind in ("gqa", "gqa_cross"):
        # head-major 3-D projections: shard the HEAD axis (never within a
        # head); replicate attention entirely when H (or Hkv) doesn't divide
        # tp (llama3.2-3b H=24, qwen2.5 H=40, whisper H=20 — noted in
        # DESIGN.md §5 as a hillclimb target).
        H_eff, Hkv_eff = H, Hkv
        if cfg.pad_attn_heads and tp > 1:
            H_eff = -(-H // tp) * tp  # pad q heads up to the TP degree
        qspec = P(None, "model", None) if _tp(H_eff, tp) else P()
        kvspec = P(None, "model", None) if _tp(Hkv_eff, tp) else P()
        ospec = P("model", None, None) if _tp(H_eff, tp) else P()
        import numpy as _np
        sq = 1.0 / float(_np.sqrt(d))
        so = 1.0 / float(_np.sqrt(H * hd))

        def _padded(shape):
            return tuple(H_eff if x == H else x for x in shape)

        for k, s in att.gqa_params_shape(cfg).items():
            s = _padded(s)
            if k == "wq":
                t[k] = Leaf(s, qspec, scale=sq)
            elif k in ("wk", "wv"):
                t[k] = Leaf(s, kvspec, scale=sq)
            elif k == "wo":
                t[k] = Leaf(s, ospec, scale=so)
            elif k == "bq":
                t[k] = Leaf(s, P("model", None) if _tp(H, tp) else P())
            elif k in ("bk", "bv"):
                t[k] = Leaf(s, P("model", None) if _tp(Hkv, tp) else P())
            else:
                t[k] = Leaf(s, P())
        if kind == "gqa_cross":  # extra cross-attention projections
            t["cq"] = Leaf((d, H, hd), qspec, scale=sq)
            t["ck"] = Leaf((d, Hkv, hd), kvspec, scale=sq)
            t["cv"] = Leaf((d, Hkv, hd), kvspec, scale=sq)
            t["co"] = Leaf((H, hd, d), ospec, scale=so)
            t["norm_cross"] = Leaf((d,))
    elif kind == "mla":
        for k, s in att.mla_params_shape(cfg).items():
            if k in ("wq_b", "wk_b", "wv_b"):
                t[k] = Leaf(s, col(s))
            elif k == "wo":
                t[k] = Leaf(s, row(s))
            else:
                t[k] = Leaf(s, P())
    elif kind == "mamba":
        mc = cfg.mamba
        di = mc.expand * d
        shp = mam.mamba_params_shape(cfg)
        spec = {
            "w_in": P(None, "model") if _tp(2 * di, tp) else P(),
            "conv_w": P(None, "model") if _tp(di, tp) else P(),
            "conv_b": P("model") if _tp(di, tp) else P(),
            "w_bcdt": P("model", None) if _tp(di, tp) else P(),
            "w_dt": P(None, "model") if _tp(di, tp) else P(),
            "dt_bias": P("model") if _tp(di, tp) else P(),
            "A_log": P("model", None) if _tp(di, tp) else P(),
            "D": P("model") if _tp(di, tp) else P(),
            "w_out": P("model", None) if _tp(di, tp) else P(),
        }
        t = {k: Leaf(s, spec[k], dtype="float32" if k in ("A_log", "D", "dt_bias")
                     else cfg.dtype) for k, s in shp.items()}
    elif kind == "rwkv":
        shp = rwkv_mod.rwkv_params_shape(cfg)
        for k, s in shp.items():
            if k in ("w_r", "w_k", "w_v", "w_g", "c_k"):
                t[k] = Leaf(s, P(None, "model") if _tp(s[-1], tp) else P())
            elif k in ("w_o", "c_v"):
                t[k] = Leaf(s, P("model", None) if _tp(s[0], tp) else P())
            elif k in ("w0", "u"):
                t[k] = Leaf(s, P("model", None) if _tp(s[0], tp) else P(),
                            dtype="float32")
            else:
                t[k] = Leaf(s, P())
    else:
        raise ValueError(kind)
    t["norm"] = Leaf((d,))
    return t


def _ffn_template(kind: str, cfg: ModelConfig, tp: int):
    d, f = cfg.d_model, cfg.d_ff
    t = {}
    if kind == "mlp":
        t["w_gate"] = Leaf((d, f), P(None, "model") if _tp(f, tp) else P())
        t["w_up"] = Leaf((d, f), P(None, "model") if _tp(f, tp) else P())
        t["w_down"] = Leaf((f, d), P("model", None) if _tp(f, tp) else P())
    elif kind == "moe":
        m = cfg.moe
        ep = P("model", None, None) if _tp(m.num_experts, tp) else P()
        t["router"] = Leaf((d, m.num_experts), P())
        t["w_gate"] = Leaf((m.num_experts, d, m.d_ff_expert), ep)
        t["w_up"] = Leaf((m.num_experts, d, m.d_ff_expert), ep)
        t["w_down"] = Leaf((m.num_experts, m.d_ff_expert, d), ep)
        if m.num_shared:
            fs = m.d_ff_expert * m.num_shared
            t["s_gate"] = Leaf((d, fs), P(None, "model") if _tp(fs, tp) else P())
            t["s_up"] = Leaf((d, fs), P(None, "model") if _tp(fs, tp) else P())
            t["s_down"] = Leaf((fs, d), P("model", None) if _tp(fs, tp) else P())
    elif kind == "rwkv_cm":
        pass  # rwkv channel-mix params live in the mixer template (shared dict)
    elif kind == "none":
        pass
    else:
        raise ValueError(kind)
    if kind not in ("rwkv_cm", "none"):
        t["norm"] = Leaf((d,))
    return t


def param_template(cfg: ModelConfig, tp: int = 1):
    """Full parameter template tree: {embed, stages[...], final_norm, ...}."""
    d, V = cfg.d_model, cfg.vocab_size
    t: dict[str, Any] = {
        "embed": Leaf((V, d), P("model", None) if _tp(V, tp) else P(),
                      scale=0.02),
        "final_norm": Leaf((d,)),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = Leaf((V, d), P("model", None) if _tp(V, tp) else P(),
                            scale=0.02)
    stages = []
    for repeat, group in make_program(cfg):
        gt = []
        for mixer, ffn in group:
            gt.append({"mixer": _mixer_template(mixer, cfg, tp),
                       "ffn": _ffn_template(ffn, cfg, tp)})
        # prepend the scan axis to every leaf
        gt = jax.tree.map(
            lambda lf: Leaf((repeat, *lf.shape), _stack_spec(lf.spec),
                            lf.dtype, lf.scale),
            gt, is_leaf=lambda x: isinstance(x, Leaf))
        stages.append(gt)
    t["stages"] = stages
    if cfg.is_encdec:
        enc = {"mixer": _mixer_template("gqa", cfg, tp),
               "ffn": _ffn_template("mlp", cfg, tp)}
        enc = jax.tree.map(
            lambda lf: Leaf((cfg.encoder_layers, *lf.shape),
                            _stack_spec(lf.spec), lf.dtype, lf.scale),
            enc, is_leaf=lambda x: isinstance(x, Leaf))
        t["encoder"] = enc
        t["enc_final_norm"] = Leaf((d,))
    if cfg.mtp:
        t["mtp"] = {"mixer": _mixer_template(
            "mla" if cfg.attn_kind == "mla" else "gqa", cfg, tp),
            "ffn": _ffn_template("mlp", cfg, tp),
            "proj": Leaf((2 * d, d), P())}
    if cfg.vision_tokens:
        t["vision_proj"] = Leaf((d, d), P())  # stub anyres projector
    if cfg.is_encdec:
        t["frame_proj"] = Leaf((d, d), P())  # stub conv-frontend projector
    return t


def _stack_spec(spec: P) -> P:
    return P(None, *spec)


def init_params(cfg: ModelConfig, seed: int = 0, tp: int = 1):
    """Concrete random init (smoke/test scale)."""
    kg = KeyGen(seed)
    tmpl = param_template(cfg, tp)

    def mk(path, lf: Leaf):
        dt = jnp.bfloat16 if lf.dtype == "bfloat16" else jnp.float32
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        if lf.shape and any(s == 0 for s in lf.shape):
            return jnp.zeros(lf.shape, dt)
        # name-dispatched special leaves (independent of the scan-stack dim)
        if "norm" in name or name == "ln_x":
            return jnp.ones(lf.shape, dt)
        if name.startswith("b") or name in ("dt_bias", "conv_b"):
            return jnp.zeros(lf.shape, dt)
        if name.startswith("mu_"):
            return jnp.full(lf.shape, 0.5, dt)
        if name == "w0":  # rwkv decay base: mild decay
            return jnp.full(lf.shape, -1.0, dt)
        if name == "u":
            return (jax.random.normal(kg(), lf.shape, jnp.float32) * 0.1
                    ).astype(dt)
        if name == "A_log":
            return jnp.log(jnp.broadcast_to(
                jnp.arange(1, lf.shape[-1] + 1, dtype=jnp.float32),
                lf.shape)).astype(dt)
        if name == "D":
            return jnp.ones(lf.shape, dt)
        if len(lf.shape) >= 2:
            fan_in = lf.shape[-2]
            scale = lf.scale if lf.scale is not None else 1.0 / np.sqrt(fan_in)
            return (jax.random.normal(kg(), lf.shape, jnp.float32) * scale
                    ).astype(dt)
        return (jax.random.normal(kg(), lf.shape, jnp.float32) * 0.1).astype(dt)

    return jax.tree_util.tree_map_with_path(
        mk, tmpl, is_leaf=lambda x: isinstance(x, Leaf))


def abstract_params(cfg: ModelConfig, tp: int = 1):
    tmpl = param_template(cfg, tp)
    return jax.tree.map(
        lambda lf: jax.ShapeDtypeStruct(
            lf.shape, jnp.bfloat16 if lf.dtype == "bfloat16" else jnp.float32),
        tmpl, is_leaf=lambda x: isinstance(x, Leaf))


def param_pspecs(cfg: ModelConfig, tp: int = 1):
    tmpl = param_template(cfg, tp)
    return jax.tree.map(lambda lf: lf.spec, tmpl,
                        is_leaf=lambda x: isinstance(x, Leaf))


# ------------------------------------------------------------- layer apply
def _apply_mixer(kind, p, x, cfg, *, positions, mode, cache, enc_out=None,
                 mesh=None):
    del mesh  # mixers shard via GSPMD param specs alone
    h = rms_norm(x, p["norm"])
    if kind in ("gqa", "gqa_cross"):
        if mode == "train":
            out = att.gqa_apply(p, h, cfg, positions=positions, mode="train")
            new_cache = None
        else:
            out, new_cache = att.gqa_apply(p, h, cfg, positions=positions,
                                           mode=mode, cache=cache)
        x = x + out
        if kind == "gqa_cross":
            x = x + _cross_attn(p, rms_norm(x, p["norm_cross"]), enc_out, cfg)
        return x, new_cache
    if kind == "mla":
        if mode == "train":
            return x + att.mla_apply(p, h, cfg, positions=positions,
                                     mode="train"), None
        out, new_cache = att.mla_apply(p, h, cfg, positions=positions,
                                       mode=mode, cache=cache)
        return x + out, new_cache
    if kind == "mamba":
        if mode == "train":
            return x + mam.mamba_apply(p, h, cfg, mode="train"), None
        out, new_cache = mam.mamba_apply(p, h, cfg, mode=mode, cache=cache)
        return x + out, new_cache
    if kind == "rwkv":
        if mode == "train":
            return x + rwkv_mod.time_mix(p, h, cfg, mode="train"), None
        out, new_cache = rwkv_mod.time_mix(p, h, cfg, mode=mode, cache=cache)
        return x + out, new_cache
    raise ValueError(kind)


def _cross_attn(p, h, enc_out, cfg):
    """Decoder cross-attention to (B, Se, d) encoder output (whisper)."""
    H = cfg.num_heads
    q = jnp.einsum("bsd,dhk->bshk", h, p["cq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out.astype(h.dtype), p["ck"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out.astype(h.dtype), p["cv"])
    o = att.flash_attention(q, att.repeat_kv(k, H), att.repeat_kv(v, H),
                            causal=False)
    return jnp.einsum("bshk,hkd->bsd", o.astype(h.dtype), p["co"])


def _apply_ffn(kind, p, x, cfg, mixer_p, *, mode, cache, mesh=None,
               expert_stack=None, layer_idx=None):
    if kind == "none":
        return x, jnp.float32(0.0), cache
    if kind == "rwkv_cm":
        h = rms_norm(x, mixer_p["ln_x"])
        if mode == "train":
            out = rwkv_mod.channel_mix(mixer_p, h, mode="train")
            return x + out, jnp.float32(0.0), None
        out, new_c = rwkv_mod.channel_mix(mixer_p, h, mode=mode, cache=cache)
        return x + out, jnp.float32(0.0), new_c
    h = rms_norm(x, p["norm"])
    if kind == "mlp":
        out = (silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        return x + out, jnp.float32(0.0), cache
    if kind == "moe":
        import numpy as _np
        dsz = (int(_np.prod([mesh.shape[a] for a in ("pod", "data")
                             if a in mesh.axis_names])) if mesh is not None
               else 1)
        tiny = x.shape[0] * x.shape[1] <= 64
        if cfg.moe_gather_decode and tiny and mode == "decode":
            out, aux = moe_mod.moe_gather_apply(p, h, cfg, stacks=expert_stack,
                                                layer_idx=layer_idx)
        elif (mesh is not None and mesh.shape.get("model", 1) > 1
                and x.shape[0] % max(dsz, 1) == 0):
            out, aux = moe_mod.moe_spmd(p, h, cfg, mesh)
        else:
            out, aux = moe_mod.moe_apply_binned(
                p, h, cfg, capacity_factor=cfg.moe.capacity_factor)
        return x + out, aux, cache
    raise ValueError(kind)


# --------------------------------------------------------------- the model
class LM:
    """Pure-functional model bound to a config (+ optional mesh for the
    shard_map sub-programs: vocab-parallel embed/CE, replicated-EP MoE)."""

    def __init__(self, cfg: ModelConfig, tp: int = 1, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.tp = mesh.shape.get("model", 1) if mesh is not None else tp
        # batch dims shard over ('pod','data') on a multi-pod mesh
        self.batch_axes = (
            ("pod", "data") if mesh is not None and "pod" in mesh.axis_names
            else "data")
        self.program = make_program(cfg)

    @property
    def _vocab_parallel(self) -> bool:
        return (self.mesh is not None and self.tp > 1
                and self.cfg.vocab_size % self.tp == 0)

    @property
    def _data_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in ("pod", "data")
                            if a in self.mesh.axis_names]))

    def _batch_shardable(self, b: int) -> bool:
        return b % max(self._data_size, 1) == 0

    # ---- parameter plumbing
    def init(self, seed: int = 0):
        return init_params(self.cfg, seed, self.tp)

    def abstract(self):
        return abstract_params(self.cfg, self.tp)

    def pspecs(self):
        return param_pspecs(self.cfg, self.tp)

    # ---- embedding / unembedding (vocab-parallel under shard_map)
    def _embed(self, params, tokens):
        emb = params["embed"]
        if not self._vocab_parallel or not self._batch_shardable(tokens.shape[0]):
            # small-batch decode (e.g. long_500k B=1): plain gather; GSPMD
            # gathers the vocab shard — acceptable at one token/step
            return emb[tokens].astype(_dtype(self.cfg))

        def body(emb_l, tok_l):
            vloc = emb_l.shape[0]
            m = jax.lax.axis_index("model")
            rel = tok_l.astype(jnp.int32) - m * vloc
            ok = (rel >= 0) & (rel < vloc)
            e = emb_l[jnp.clip(rel, 0, vloc - 1)]
            e = jnp.where(ok[..., None], e, 0)
            return jax.lax.psum(e, "model")

        ba = self.batch_axes
        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(P("model", None), P(ba, None)),
            out_specs=P(ba, None, None))
        return fn(emb, tokens).astype(_dtype(self.cfg))

    def _unembed_logits(self, params, h):
        emb = params.get("lm_head", params["embed"])
        return h @ emb.T.astype(h.dtype)

    # ---- encoder (whisper)
    def _encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(_dtype(cfg)) @ params["frame_proj"]
        pos = jnp.arange(frames.shape[1])[None]

        def body(carry, lp):
            h = rms_norm(carry, lp["mixer"]["norm"])
            # bidirectional attention (no causal mask), no RoPE (whisper uses
            # learned positions; stub frontend already carries position info)
            B, S, d = h.shape
            H = cfg.num_heads
            qq = jnp.einsum("bsd,dhk->bshk", h, lp["mixer"]["wq"])
            kk = jnp.einsum("bsd,dhk->bshk", h, lp["mixer"]["wk"])
            vv = jnp.einsum("bsd,dhk->bshk", h, lp["mixer"]["wv"])
            o = att.flash_attention(qq, att.repeat_kv(kk, H),
                                    att.repeat_kv(vv, H), causal=False)
            x1 = carry + jnp.einsum("bshk,hkd->bsd", o.astype(h.dtype),
                                    lp["mixer"]["wo"])
            h2 = rms_norm(x1, lp["ffn"]["norm"])
            out = (silu(h2 @ lp["ffn"]["w_gate"]) * (h2 @ lp["ffn"]["w_up"])
                   ) @ lp["ffn"]["w_down"]
            return x1 + out, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return rms_norm(x, params["enc_final_norm"])

    # ---- full forward (train / prefill / decode)
    def _stack(self, params, x, *, positions, mode, caches, enc_out, remat,
               length=None):
        """Run all stages. caches: per-stage pytrees stacked on the scan axis
        (None in train mode); ``length`` is the shared per-row cache write
        position (decode). Returns (x, aux_loss, new_caches)."""
        cfg = self.cfg
        aux_total = jnp.float32(0.0)
        new_caches = []
        gather_moe = (cfg.moe_gather_decode and mode == "decode"
                      and cfg.moe is not None)
        for s_idx, ((repeat, group), sp) in enumerate(
                zip(self.program, params["stages"])):
            cache_s = None if caches is None else caches[s_idx]
            expert_stacks = [None] * len(group)
            if gather_moe:
                # strip stacked expert banks out of the scanned xs; the body
                # gathers routed slices from them by (layer, expert) index
                sp = [dict(layer) for layer in sp]
                for li, layer in enumerate(sp):
                    fp = dict(layer["ffn"])
                    ex = {k: fp.pop(k) for k in ("w_gate", "w_up", "w_down")
                          if k in fp and getattr(fp[k], "ndim", 0) == 4}
                    if ex:
                        expert_stacks[li] = ex
                        layer["ffn"] = fp

            def body(x, scanned, group=group, expert_stacks=expert_stacks):
                if caches is None:
                    lp, layer_i = scanned[0], scanned[-1]
                    cache_g = None
                else:
                    lp, cache_g, layer_i = scanned
                aux_g = jnp.float32(0.0)
                new_cache_g = []
                for li, (mixer, ffn) in enumerate(group):
                    mp = lp[li]["mixer"]
                    fp = lp[li]["ffn"]
                    c_m = None if cache_g is None else cache_g[li]["mixer"]
                    c_f = None if cache_g is None else cache_g[li]["ffn"]
                    if c_m is not None and mixer in ("gqa", "gqa_cross", "mla"):
                        c_m = (*c_m, length)  # per-row write position
                    x, nc_m = _apply_mixer(mixer, mp, x, cfg,
                                           positions=positions, mode=mode,
                                           cache=c_m, enc_out=enc_out,
                                           mesh=self.mesh)
                    x, aux, nc_f = _apply_ffn(ffn, fp, x, cfg, mp,
                                              mode=mode, cache=c_f,
                                              mesh=self.mesh,
                                              expert_stack=expert_stacks[li],
                                              layer_idx=layer_i)
                    aux_g = aux_g + aux
                    new_cache_g.append({"mixer": nc_m, "ffn": nc_f})
                return x, (aux_g, new_cache_g)

            body_fn = body
            if remat and mode == "train":
                body_fn = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)

            layer_ids = jnp.arange(repeat)
            xs = ((sp, layer_ids) if caches is None
                  else (sp, cache_s, layer_ids))
            x, (auxes, new_cache_s) = jax.lax.scan(body_fn, x, xs)
            aux_total = aux_total + jnp.sum(auxes)
            new_caches.append(new_cache_s)
        return x, aux_total, (None if caches is None else new_caches)

    def _inputs_embed(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        if cfg.vision_tokens:
            vis = batch["patches"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([vis, x], axis=1)
        return x

    def train_loss(self, params, batch, *, remat=True):
        """-> (loss, metrics). batch: tokens/labels (+patches/frames)."""
        cfg = self.cfg
        x = self._inputs_embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)[None]
        enc_out = self._encode(params, batch["frames"]) if cfg.is_encdec else None
        x, aux, _ = self._stack(params, x, positions=positions, mode="train",
                                caches=None, enc_out=enc_out, remat=remat)
        x = rms_norm(x, params["final_norm"])
        if cfg.vision_tokens:  # only text positions carry loss
            x = x[:, cfg.vision_tokens:]
        labels = batch["labels"]
        loss = self._ce(params, x, labels)
        if cfg.mtp:
            loss = loss + 0.1 * self._mtp_loss(params, x, labels)
        if cfg.moe:
            loss = loss + cfg.moe.aux_loss_coef * aux / max(cfg.num_layers, 1)
        return loss, {"ce": loss, "aux": aux}

    def _ce(self, params, h, labels):
        """Chunked-over-S cross entropy; logits never materialize unsharded.

        Vocab-parallel (Megatron-style) under shard_map when a model axis is
        available: each rank computes its local-vocab logits chunk, the
        logsumexp and gold-logit pick reduce with one psum pair.
        """
        emb = params.get("lm_head", params["embed"])
        B, S, _ = h.shape
        vocab_parallel = self._vocab_parallel and self._batch_shardable(B)

        def chunked(fn, S):
            chunk = max(1, min(512, S))
            n = S // chunk if S % chunk == 0 else 1
            return fn, S // n if n else S, n

        if not vocab_parallel:
            def body(acc, i):
                hs = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
                ls = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
                logits = (hs @ emb.T.astype(hs.dtype)).astype(jnp.float32)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
                return acc + jnp.sum(lse - gold), None

            _, chunk, n = chunked(None, S)
            tot, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(n))
            return tot / (B * S)

        _, chunk, n = chunked(None, S)

        def spmd(emb_l, h_l, lab_l):
            vloc = emb_l.shape[0]
            m = jax.lax.axis_index("model")

            def body(acc, i):
                hs = jax.lax.dynamic_slice_in_dim(h_l, i * chunk, chunk, 1)
                ls = jax.lax.dynamic_slice_in_dim(lab_l, i * chunk, chunk, 1)
                logits = (hs @ emb_l.T.astype(hs.dtype)).astype(jnp.float32)
                # max-shift is a constant for AD purposes (classic lse trick);
                # stop_gradient BEFORE pmax so the collective sees a symbolic
                # zero tangent (pmax has no JVP rule).
                lmax = jax.lax.pmax(
                    jax.lax.stop_gradient(jnp.max(logits, axis=-1)), "model")
                z = jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1)
                lse = jnp.log(jax.lax.psum(z, "model")) + lmax
                rel = ls.astype(jnp.int32) - m * vloc
                ok = (rel >= 0) & (rel < vloc)
                g = jnp.take_along_axis(
                    logits, jnp.clip(rel, 0, vloc - 1)[..., None], axis=-1)[..., 0]
                gold = jax.lax.psum(jnp.where(ok, g, 0.0), "model")
                return acc + jnp.sum(lse - gold), None

            ba = self.batch_axes
            init = jax.lax.pvary(jnp.float32(0.0),
                                 (ba,) if isinstance(ba, str) else tuple(ba))
            tot, _ = jax.lax.scan(body, init, jnp.arange(n))
            return tot[None]

        ba = self.batch_axes
        fn = shard_map(
            spmd, mesh=self.mesh,
            in_specs=(P("model", None), P(ba, None, None), P(ba, None)),
            out_specs=P((ba,) if isinstance(ba, str) else ba))
        return jnp.sum(fn(emb, h, labels)) / (B * S)

    def _mtp_loss(self, params, h, labels):
        """Deepseek MTP: one extra block predicts token t+2 from [h_t; e_{t+1}]."""
        cfg = self.cfg
        B, S, d = h.shape
        e_next = self._embed(params, labels)  # embedding of token t+1
        z = jnp.concatenate([h[:, :-1], e_next[:, :-1]], axis=-1) @ \
            params["mtp"]["proj"].astype(h.dtype)
        pos = jnp.arange(S - 1)[None]
        z, _ = _apply_mixer(self.cfg.attn_kind if cfg.attn_kind == "mla" else "gqa",
                            params["mtp"]["mixer"], z, cfg,
                            positions=pos, mode="train", cache=None)
        z, _, _ = _apply_ffn("mlp", params["mtp"]["ffn"], z, cfg,
                             params["mtp"]["mixer"], mode="train", cache=None)
        lbl2 = labels[:, 1:]
        return self._ce(params, z, lbl2)

    # ---- serving -----------------------------------------------------------
    def cache_template(self, batch: int, max_seq: int):
        """Pytree of (shape, dtype, pspec) Leafs describing the decode cache."""
        cfg = self.cfg
        dt = _dtype(cfg)
        Hkv, hd = cfg.num_kv_heads, cfg.head_dim
        long_ctx = batch == 1  # long_500k: shard the sequence, not the batch
        seq_axis = "data" if long_ctx else (
            "model" if cfg.cache_seq_shard else None)
        b_axis = None if long_ctx else "data"

        def mixer_cache(kind, repeat):
            if kind in ("gqa", "gqa_cross"):
                # cross-attn K/V (whisper) are recomputed from the encoder
                # stub each step, so only the self-attn cache is stored.
                hd_axis = ("model" if (_tp(hd, self.tp)
                                        and seq_axis != "model") else None)
                kv = Leaf((repeat, batch, max_seq, Hkv, hd),
                          P(None, b_axis, seq_axis, None, hd_axis),
                          dtype=cfg.dtype)
                return {"k": kv, "v": kv}
            if kind == "mla":
                m = cfg.mla
                return {
                    "c": Leaf((repeat, batch, max_seq, m.kv_lora_rank),
                              P(None, b_axis, seq_axis, None), dtype=cfg.dtype),
                    "r": Leaf((repeat, batch, max_seq, m.qk_rope_head_dim),
                              P(None, b_axis, seq_axis, None), dtype=cfg.dtype),
                }
            if kind == "mamba":
                di = cfg.mamba.expand * cfg.d_model
                return {
                    "h": Leaf((repeat, batch, di, cfg.mamba.d_state),
                              P(None, b_axis, "model" if _tp(di, self.tp) else None,
                                None), dtype="float32"),
                    "tail": Leaf((repeat, batch, cfg.mamba.d_conv - 1, di),
                                 P(None, b_axis, None,
                                   "model" if _tp(di, self.tp) else None),
                                 dtype=cfg.dtype),
                }
            if kind == "rwkv":
                hs = cfg.rwkv_head_size
                H = cfg.d_model // hs
                return {
                    "x": Leaf((repeat, batch, cfg.d_model), P(None, b_axis, None),
                              dtype=cfg.dtype),
                    "s": Leaf((repeat, batch, H, hs, hs),
                              P(None, b_axis, "model" if _tp(H, self.tp) else None,
                                None, None), dtype="float32"),
                }
            raise ValueError(kind)

        stages = []
        for repeat, group in self.program:
            g = []
            for mixer, ffn in group:
                c = {"mixer": mixer_cache(mixer, repeat),
                     "ffn": (Leaf((repeat, batch, cfg.d_model),
                                  P(None, b_axis, None), dtype=cfg.dtype)
                             if ffn == "rwkv_cm" else None)}
                g.append(c)
            stages.append(g)
        t = {"stages": stages,
             "length": Leaf((batch,), P(b_axis), dtype="int32")}
        del dt
        return t

    def init_cache(self, batch: int, max_seq: int):
        tmpl = self.cache_template(batch, max_seq)
        return jax.tree.map(
            lambda lf: jnp.zeros(lf.shape, _np_dtype(lf.dtype)),
            tmpl, is_leaf=lambda x: isinstance(x, Leaf))

    def _caches_to_tuples(self, cache, mode):
        """Convert the dict cache into the per-mixer tuple forms (+length)."""
        length = cache["length"]
        out = []
        for (repeat, group), stage_c in zip(self.program, cache["stages"]):
            g = []
            for (mixer, ffn), c in zip(group, stage_c):
                mc = c["mixer"]
                if mixer in ("gqa", "gqa_cross"):
                    tup = (mc["k"], mc["v"])
                elif mixer == "mla":
                    tup = (mc["c"], mc["r"])
                elif mixer == "mamba":
                    tup = (mc["h"], mc["tail"])
                elif mixer == "rwkv":
                    tup = (mc["x"], mc["s"])
                else:
                    raise ValueError(mixer)
                g.append({"mixer": tup, "ffn": c["ffn"]})
            out.append(g)
        return out, length

    def _tuples_to_caches(self, new_caches, cache, new_length):
        """Write updated tuples back into the dict structure."""
        out_stages = []
        for (repeat, group), stage_c, stage_n in zip(
                self.program, cache["stages"], new_caches):
            g = []
            for (mixer, ffn), c_old, c_new in zip(group, stage_c, stage_n):
                t = c_new["mixer"]
                if mixer in ("gqa", "gqa_cross"):
                    mc = dict(c_old["mixer"], k=t[0], v=t[1])
                elif mixer == "mla":
                    mc = dict(c_old["mixer"], c=t[0], r=t[1])
                elif mixer == "mamba":
                    mc = {"h": t[0], "tail": t[1]}
                elif mixer == "rwkv":
                    mc = {"x": t[0], "s": t[1]}
                g.append({"mixer": mc, "ffn": c_new["ffn"]})
            out_stages.append(g)
        return {"stages": out_stages, "length": new_length}

    def decode_step(self, params, tokens, cache, *, enc_out=None):
        """One token for every sequence. tokens (B,1) -> (logits (B,V), cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        caches, length = self._caches_to_tuples(cache, "decode")
        positions = length[:, None]
        if cfg.is_encdec and enc_out is None:
            # cross-attn K/V are precomputed into the cache at prefill; for
            # the dry-run serve_step we recompute from a zero encoder stub.
            enc_out = jnp.zeros((tokens.shape[0], cfg.encoder_seq, cfg.d_model),
                                _dtype(cfg))
        x, _, new_caches = self._stack(params, x, positions=positions,
                                       mode="decode", caches=caches,
                                       enc_out=enc_out, remat=False,
                                       length=length)
        x = rms_norm(x, params["final_norm"])
        logits = self._unembed_logits(params, x[:, 0])
        new_cache = self._tuples_to_caches(new_caches, cache, length + 1)
        return logits, new_cache

    def prefill(self, params, batch):
        """Full-sequence forward building a decode cache is exercised via
        chunked prefill in repro.serve; here: logits for all positions."""
        cfg = self.cfg
        x = self._inputs_embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)[None]
        enc_out = self._encode(params, batch["frames"]) if cfg.is_encdec else None
        x, _, _ = self._stack(params, x, positions=positions, mode="train",
                              caches=None, enc_out=enc_out, remat=False)
        x = rms_norm(x, params["final_norm"])
        return self._unembed_logits(params, x[:, -1])


def _np_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "int32": jnp.int32}[name]
