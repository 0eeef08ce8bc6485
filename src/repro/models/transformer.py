"""Unified model assembly for all assigned architectures.

A model is a repeating ``block_unit`` of layer kinds scanned ``repeats`` times
(MaxText-style scan-over-layers keeps compile time and HLO size independent of
depth).  Kinds:

  'attn'         full attention + dense FFN
  'local'        sliding-window attention + dense FFN (gemma2 local layers)
  'moe'          full attention + mixture-of-experts FFN
  'mamba'        Mamba-2 SSD mixer block
  'rwkv'         RWKV-6 time-mix + channel-mix block
  'shared_attn'  attention + FFN whose weights are SHARED across repeats
                 (zamba2's shared transformer block)

Three entry points per model: ``loss`` (training), ``prefill`` (build caches),
``decode_step`` (one token against caches).  Heads: 'lm' (causal LM) or
'frame' (encoder-only frame classification, hubert).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_lib
from . import mamba as mamba_lib
from . import mlp as mlp_lib
from . import rwkv as rwkv_lib
from .common import (
    Initializer, LogicalAxes, cross_entropy_loss, logical_constraint,
    make_mrope_positions, rms_norm, softcap,
)

PyTree = Any

__all__ = ["ModelConfig", "Model"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    block_unit: Tuple[str, ...] = ("attn",)
    causal: bool = True
    head: str = "lm"               # 'lm' | 'frame'
    tie_embeddings: bool = True
    scale_embeddings: bool = False
    activation: str = "silu"
    norm_plus_one: bool = False    # gemma convention
    use_post_norm: bool = False    # gemma2 post-block norms
    use_bias: bool = False
    qk_norm: bool = False
    # attention
    sliding_window: Optional[int] = None
    attn_softcap: Optional[float] = None
    logit_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None
    attn_impl: str = "xla"
    # moe
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    dense_residual: bool = False
    moe_d_ff: Optional[int] = None           # routed-expert hidden size
    capacity_factor: float = 1.25
    moe_dispatch: str = "auto"               # 'auto' | 'gather_tokens' 
    # ssm
    ssm_state: int = 64
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    # modality frontends (stubs)
    n_vision_tokens: int = 0
    vision_grid: Tuple[int, int] = (16, 16)
    audio_frontend_dim: int = 0    # hubert conv-feature dim (input proj)
    # numerics
    param_dtype: Any = jnp.float32
    rwkv_chunk: int = 0            # >0: chunked RWKV time-mix (perf path)
    rwkv_chunk_bf16: bool = False  # bf16 chunk operands
    rwkv_pallas: bool = False      # chunked wkv via the Pallas kernel
    remat: str = "block"           # 'block' (checkpoint each scanned unit) | 'none'

    def __post_init__(self):
        if self.n_layers % len(self.block_unit):
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} not divisible by "
                f"block unit {self.block_unit}"
            )

    @property
    def repeats(self) -> int:
        return self.n_layers // len(self.block_unit)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    # -- sub-configs -------------------------------------------------------
    def attn_cfg(self, kind: str) -> attn_lib.AttentionConfig:
        return attn_lib.AttentionConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.hd,
            causal=self.causal,
            sliding_window=self.sliding_window if kind == "local" else None,
            attn_softcap=self.attn_softcap,
            rope_theta=self.rope_theta,
            mrope_sections=self.mrope_sections,
            use_bias=self.use_bias,
            qk_norm=self.qk_norm,
            attn_impl=self.attn_impl,
        )

    def mlp_cfg(self) -> mlp_lib.MLPConfig:
        return mlp_lib.MLPConfig(self.d_model, self.d_ff, self.activation, self.use_bias)

    def moe_cfg(self) -> mlp_lib.MoEConfig:
        return mlp_lib.MoEConfig(
            d_model=self.d_model,
            d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.n_experts,
            top_k=self.top_k,
            n_shared_experts=self.n_shared_experts,
            dense_residual=self.dense_residual,
            dense_d_ff=self.d_ff,
            capacity_factor=self.capacity_factor,
            activation=self.activation,
            dispatch_layout=self.moe_dispatch,
        )

    def mamba_cfg(self) -> mamba_lib.MambaConfig:
        return mamba_lib.MambaConfig(
            d_model=self.d_model,
            d_inner=self.ssm_expand * self.d_model,
            state_dim=self.ssm_state,
            head_dim=self.ssm_head_dim,
            chunk=self.ssm_chunk,
        )

    def rwkv_cfg(self) -> rwkv_lib.RWKVConfig:
        return rwkv_lib.RWKVConfig(
            self.d_model, self.d_ff, head_dim=64, chunk=self.rwkv_chunk,
            chunk_bf16=self.rwkv_chunk_bf16, use_pallas=self.rwkv_pallas,
        )

    def param_count(self, params: PyTree) -> int:
        return sum(
            int(np_prod(p.shape)) for p in jax.tree.leaves(params) if hasattr(p, "shape")
        )


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


class Model:
    """Functional model bound to a ModelConfig."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # parameter construction
    # ------------------------------------------------------------------
    def _init_element(self, kind: str, ini: Initializer) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        p: Dict[str, Any] = {"norm1": ini.param((d,), ("embed",), init="ones")}
        if kind in ("attn", "local", "moe", "shared_attn"):
            p["attn"] = attn_lib.init_attention(cfg.attn_cfg(kind), ini)
            p["norm2"] = ini.param((d,), ("embed",), init="ones")
            if kind == "moe":
                p["ffn"] = mlp_lib.init_moe(cfg.moe_cfg(), ini)
            else:
                p["ffn"] = mlp_lib.init_mlp(cfg.mlp_cfg(), ini)
            if cfg.use_post_norm:
                p["post_norm1"] = ini.param((d,), ("embed",), init="ones")
                p["post_norm2"] = ini.param((d,), ("embed",), init="ones")
        elif kind == "mamba":
            p["mamba"] = mamba_lib.init_mamba(cfg.mamba_cfg(), ini)
        elif kind == "rwkv":
            p["norm2"] = ini.param((d,), ("embed",), init="ones")
            p["rwkv"] = rwkv_lib.init_rwkv(cfg.rwkv_cfg(), ini)
        else:
            raise ValueError(kind)
        return p

    def _stack_element(self, kind: str, key, mode: str, dtype):
        """Stacked (repeats, ...) params for one block-unit element."""
        cfg = self.cfg
        if mode == "params":
            keys = jax.random.split(key, cfg.repeats)

            def one(k):
                return self._init_element(kind, Initializer("params", k, dtype))

            return jax.vmap(one)(keys)
        ini = Initializer(mode, None, dtype)
        elem = self._init_element(kind, ini)
        if mode == "specs":
            return jax.tree.map(
                lambda l: LogicalAxes(("layers",) + l.names, (cfg.repeats,) + l.shape),
                elem,
                is_leaf=lambda l: isinstance(l, LogicalAxes),
            )
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.repeats,) + s.shape, s.dtype), elem
        )

    def _build(self, mode: str, key=None, dtype=None) -> PyTree:
        cfg = self.cfg
        dtype = dtype or cfg.param_dtype
        if mode == "params":
            top_key, *block_keys = jax.random.split(key, len(cfg.block_unit) + 1)
            keys = iter(block_keys)
        else:
            top_key = None
        ini_top = Initializer(mode, top_key, dtype)
        params: Dict[str, Any] = {}
        params["embed"] = ini_top.param(
            (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed", scale=0.02
        )
        if cfg.audio_frontend_dim:
            params["audio_proj"] = ini_top.param(
                (cfg.audio_frontend_dim, cfg.d_model), (None, "embed")
            )
        if cfg.n_vision_tokens:
            params["vision_proj"] = ini_top.param(
                (cfg.d_model, cfg.d_model), (None, "embed")
            )
        blocks: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.block_unit):
            bkey = next(keys) if mode == "params" else None
            if kind == "shared_attn":
                # single copy reused every repeat (zamba2's weight sharing)
                if mode == "params":
                    blocks[f"b{i}"] = self._init_element(kind, Initializer("params", bkey, dtype))
                else:
                    blocks[f"b{i}"] = self._init_element(kind, Initializer(mode, None, dtype))
            else:
                blocks[f"b{i}"] = self._stack_element(kind, bkey, mode, dtype)
        params["blocks"] = blocks
        params["final_norm"] = ini_top.param((cfg.d_model,), ("embed",), init="ones")
        if not cfg.tie_embeddings:
            params["lm_head"] = ini_top.param(
                (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), init="normal"
            )
        return params

    def init(self, key, dtype=None) -> PyTree:
        return self._build("params", key, dtype)

    def param_specs(self) -> PyTree:
        """LogicalAxes tree (resolve under axis_rules for PartitionSpecs)."""
        return self._build("specs")

    def param_shapes(self, dtype=None) -> PyTree:
        return self._build("shapes", dtype=dtype)

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def _norm(self, x, w):
        return rms_norm(x, w, plus_one=self.cfg.norm_plus_one)

    def _embed_inputs(self, params, batch, dtype=jnp.bfloat16):
        """Returns (x, positions).  positions is (B, S) or (3, B, S) for M-RoPE."""
        cfg = self.cfg
        if cfg.audio_frontend_dim:
            frames = batch["frames"].astype(dtype)          # (B, S, F) stub output
            x = jnp.einsum("bsf,fd->bsd", frames, params["audio_proj"].astype(dtype))
            positions = jnp.broadcast_to(
                jnp.arange(x.shape[1])[None], x.shape[:2]
            )
            return x, positions
        tokens = batch["tokens"]
        x = params["embed"].astype(dtype)[tokens]
        if cfg.n_vision_tokens:
            ve = batch["vision_embeds"].astype(dtype)       # (B, n_vis, d) stub
            ve = jnp.einsum("bvd,de->bve", ve, params["vision_proj"].astype(dtype))
            x = jnp.concatenate([ve, x], axis=1)
            b, s = x.shape[0], x.shape[1]
            positions = make_mrope_positions(b, s, cfg.n_vision_tokens, cfg.vision_grid)
        else:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        if cfg.scale_embeddings:
            x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(dtype)
        x = logical_constraint(x, "batch", "seq", "embed")
        return x, positions

    def _head(self, params, x):
        cfg = self.cfg
        x = self._norm(x, params["final_norm"])
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        logits = softcap(logits, cfg.logit_softcap)
        return logical_constraint(logits, "batch", "seq", "vocab")

    # ------------------------------------------------------------------
    # block application
    # ------------------------------------------------------------------
    def _apply_block(self, kind, bp, x, positions, mode, cache=None, position=None):
        """Apply one block.  mode: 'fwd' | 'prefill' | 'decode'.
        Returns (x, new_cache, aux_loss)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if kind in ("attn", "local", "moe", "shared_attn"):
            acfg = cfg.attn_cfg(kind)
            h = self._norm(x, bp["norm1"])
            # named scopes (HLO metadata only) that the profiler's op names
            # carry into the backward pass and remat as well
            with jax.named_scope("repro/attn"):
                if mode == "decode":
                    y, new_attn_cache = attn_lib.attention_decode(acfg, bp["attn"], h, position, cache["attn"])
                elif mode == "prefill":
                    y, new_attn_cache = attn_lib.attention_forward(acfg, bp["attn"], h, positions, return_cache=True)
                else:
                    y, new_attn_cache = attn_lib.attention_forward(acfg, bp["attn"], h, positions), None
            if cfg.use_post_norm:
                y = self._norm(y, bp["post_norm1"])
            x = x + y
            h = self._norm(x, bp["norm2"])
            with jax.named_scope("repro/mlp"):
                if kind == "moe":
                    y, moe_aux = mlp_lib.moe_forward(cfg.moe_cfg(), bp["ffn"], h, return_aux=(mode == "fwd"))
                    if moe_aux is not None:
                        aux = aux + moe_aux
                else:
                    y = mlp_lib.mlp_forward(cfg.mlp_cfg(), bp["ffn"], h)
            if cfg.use_post_norm:
                y = self._norm(y, bp["post_norm2"])
            x = x + y
            new_cache = {"attn": new_attn_cache} if mode != "fwd" else None
            return x, new_cache, aux
        if kind == "mamba":
            mcfg = cfg.mamba_cfg()
            h = self._norm(x, bp["norm1"])
            if mode == "decode":
                y, new_c = mamba_lib.mamba_decode(mcfg, bp["mamba"], h, cache["mamba"])
            elif mode == "prefill":
                y, new_c = mamba_lib.mamba_forward(mcfg, bp["mamba"], h, return_cache=True)
            else:
                y, new_c = mamba_lib.mamba_forward(mcfg, bp["mamba"], h), None
            x = x + y
            return x, ({"mamba": new_c} if mode != "fwd" else None), aux
        if kind == "rwkv":
            rcfg = cfg.rwkv_cfg()
            h = self._norm(x, bp["norm1"])
            if mode == "decode":
                y, tc = rwkv_lib.timemix_decode(rcfg, bp["rwkv"], h, cache["rwkv"])
            elif mode == "prefill":
                y, tc = rwkv_lib.timemix_forward(rcfg, bp["rwkv"], h, return_cache=True)
            else:
                y, tc = rwkv_lib.timemix_forward(rcfg, bp["rwkv"], h), None
            x = x + y
            h = self._norm(x, bp["norm2"])
            if mode == "decode":
                y, cc = rwkv_lib.chanmix_decode(rcfg, bp["rwkv"], h, cache["rwkv"])
            elif mode == "prefill":
                y, cc = rwkv_lib.chanmix_forward(rcfg, bp["rwkv"], h, return_cache=True)
            else:
                y, cc = rwkv_lib.chanmix_forward(rcfg, bp["rwkv"], h), None
            x = x + y
            new_cache = {"rwkv": {**tc, **cc}} if mode != "fwd" else None
            return x, new_cache, aux
        raise ValueError(kind)

    def _scan_blocks(self, params, x, positions, mode, caches=None, position=None):
        """Scan over repeats; within a repeat apply each unit element in order."""
        cfg = self.cfg

        def body(carry, xs):
            h, aux_acc = carry
            layer_params, layer_caches = xs
            new_caches = {}
            for i, kind in enumerate(cfg.block_unit):
                key = f"b{i}"
                bp = params["blocks"][key] if kind == "shared_attn" else layer_params[key]
                c = None if layer_caches is None else layer_caches[key]
                h, nc, aux = self._apply_block(kind, bp, h, positions, mode, cache=c, position=position)
                if nc is not None:
                    new_caches[key] = nc
                aux_acc = aux_acc + aux
            return (h, aux_acc), (new_caches if new_caches else None)

        stacked = {
            f"b{i}": params["blocks"][f"b{i}"]
            for i, kind in enumerate(cfg.block_unit)
            if kind != "shared_attn"
        }
        if cfg.remat == "block" and mode == "fwd":
            body = jax.checkpoint(body)
        if mode == "fwd":
            (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), (stacked, None))
            return x, None, aux
        if mode == "prefill":
            (x, aux), caches_out = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), (stacked, None)
            )
            return x, caches_out, aux
        # decode: thread caches through xs/ys
        (x, aux), caches_out = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (stacked, caches)
        )
        return x, caches_out, aux

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------
    def forward(self, params, batch, dtype=jnp.bfloat16):
        x, positions = self._embed_inputs(params, batch, dtype)
        x, _, aux = self._scan_blocks(params, x, positions, "fwd")
        return self._head(params, x), aux

    def loss(self, params, batch, dtype=jnp.bfloat16):
        cfg = self.cfg
        logits, aux = self.forward(params, batch, dtype)
        targets = batch["targets"]
        if cfg.n_vision_tokens:
            # loss only on text positions (after the vision prefix)
            logits = logits[:, cfg.n_vision_tokens :]
        mask = batch.get("mask")
        return cross_entropy_loss(logits, targets, mask) + aux

    def prefill(self, params, batch, dtype=jnp.bfloat16):
        x, positions = self._embed_inputs(params, batch, dtype)
        x, caches, _ = self._scan_blocks(params, x, positions, "prefill")
        return self._head(params, x[:, -1:]), caches

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Zero caches shaped for decode (stacked over repeats per element)."""
        cfg = self.cfg
        caches = {}
        for i, kind in enumerate(cfg.block_unit):
            if kind in ("attn", "local", "moe", "shared_attn"):
                one = {"attn": attn_lib.init_kv_cache(cfg.attn_cfg(kind), batch, max_len, dtype)}
            elif kind == "mamba":
                one = {"mamba": mamba_lib.init_mamba_cache(cfg.mamba_cfg(), batch, dtype)}
            elif kind == "rwkv":
                one = {"rwkv": rwkv_lib.init_rwkv_cache(cfg.rwkv_cfg(), batch, dtype)}
            else:
                raise ValueError(kind)
            caches[f"b{i}"] = jax.tree.map(
                lambda t: jnp.broadcast_to(t[None], (cfg.repeats,) + t.shape), one
            )
        return caches

    def decode_step(self, params, caches, tokens, position, dtype=jnp.bfloat16):
        """tokens: (B, 1) int32; position: (B,) int32.  Returns (logits, caches)."""
        cfg = self.cfg
        x = params["embed"].astype(dtype)[tokens]
        if cfg.scale_embeddings:
            x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(dtype)
        x, caches_out, _ = self._scan_blocks(
            params, x, None, "decode", caches=caches, position=position
        )
        return self._head(params, x), caches_out

    # ------------------------------------------------------------------
    def input_specs(self, seq_len: int, batch: int, for_loss: bool = True):
        """ShapeDtypeStruct stand-ins for one training batch (dry-run)."""
        cfg = self.cfg
        ii = jnp.int32
        if cfg.audio_frontend_dim:
            spec = {
                "frames": jax.ShapeDtypeStruct((batch, seq_len, cfg.audio_frontend_dim), jnp.bfloat16),
            }
            if for_loss:
                spec["targets"] = jax.ShapeDtypeStruct((batch, seq_len), ii)
            return spec
        if cfg.n_vision_tokens:
            text = seq_len - cfg.n_vision_tokens
            spec = {
                "tokens": jax.ShapeDtypeStruct((batch, text), ii),
                "vision_embeds": jax.ShapeDtypeStruct((batch, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16),
            }
            if for_loss:
                spec["targets"] = jax.ShapeDtypeStruct((batch, text), ii)
            return spec
        spec = {"tokens": jax.ShapeDtypeStruct((batch, seq_len), ii)}
        if for_loss:
            spec["targets"] = jax.ShapeDtypeStruct((batch, seq_len), ii)
        return spec
