"""Shared model components: config, param init with sharding specs, norms,
RoPE, activation-sharding helpers.

Parameter handling is pure JAX: ``init`` functions return
``(params, specs)`` twin pytrees, where ``specs`` holds a
``jax.sharding.PartitionSpec`` per array.  Spec generation is
divisibility-aware: an axis is sharded over the tensor-parallel mesh axis
only if its size divides evenly (else replicated), so every assigned
architecture lowers cleanly on the production mesh.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ModelConfig:
    name: str = "model"
    family: str = "dense"        # dense | moe | encdec | vlm | audio | ssm | hybrid
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab_size: int = 1024
    # --- attention ---------------------------------------------------------
    attention: str = "h1d"       # h1d | full | paper's baseline comparison
    nr: int = 16                 # N_r, the paper's single hyper-parameter
    causal_mode: str = "fine-q"  # fine-q (leak-free) | coarse-q (paper-faithful)
    attn_impl: str = "auto"      # auto | jnp | pallas | pallas_interpret
                                 # ('auto': the fused kernels on TPU, the
                                 # blocked XLA path elsewhere --
                                 # kernels.tuning.KernelPolicy)
    attn_tq: Optional[int] = None  # Pallas query-tile rows override
                                 # (multiple of nr); None = the policy's
                                 # tuning table picks per launch
    decode_impl: str = "auto"    # serving decode tick: auto | jnp | pallas
                                 # | pallas_interpret (fused single-launch
                                 # hierarchical-KV attend + ancestor update)
    cache_dtype: str = "fp32"    # paged KV-page storage: fp32 | int8
                                 # (int8: symmetric per-row scales, see
                                 # core.quantization; paged engine only)
    cache_quant_levels: int = -1  # with cache_dtype='int8': quantize
                                 # hierarchy levels [0, n); -1 = all
                                 # levels (coarse rows are pairwise
                                 # means -> ever-shrinking dynamic
                                 # range, so all-level is the default)
    qkv_bias: bool = False       # qwen2.x
    qk_norm: bool = False        # gemma3
    sliding_window: int = 0      # >0: local layers use block-local attention
    global_every: int = 0        # gemma3: layer i is global iff i % global_every == global_every-1
    rope_theta: float = 10_000.0
    # --- FFN / MoE ---------------------------------------------------------
    mlp_activation: str = "swiglu"   # swiglu | geglu
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0     # qwen2-moe shared expert width
    moe_dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    moe_capacity_factor: float = 1.25
    moe_aux_loss: float = 0.01
    # --- SSM (mamba2 / hybrid) ---------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 64
    hybrid_attn_every: int = 6   # zamba2: shared attention block cadence
    # --- encoder-decoder ----------------------------------------------------
    encoder_layers: int = 0
    # --- frontends (stubs per assignment) -----------------------------------
    prefix_len: int = 0          # vlm: number of patch embeddings
    # --- numerics / misc ----------------------------------------------------
    dtype: str = "float32"
    tie_embeddings: bool = False
    remat: bool = False          # activation checkpointing per layer
    force_loop: bool = False     # disable scan-over-layers (roofline
                                 # accounting: XLA cost_analysis counts
                                 # while bodies once)
    seq_parallel_residual: bool = True  # Megatron-style SP: shard the
                                 # residual sequence axis over "model"
                                 # (memory win, pays per-layer gathers)
    remat_policy: str = "dots"   # full | dots | none -- "dots" saves
                                 # matmul operands so the backward pass
                                 # does not re-gather TP activations

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def layer_uses_global_attn(self, i: int) -> bool:
        if self.global_every <= 0:
            return True
        return i % self.global_every == self.global_every - 1

    def layer_is_attn(self, i: int) -> bool:
        """hybrid (zamba2): which layers run the shared attention block."""
        return (i % self.hybrid_attn_every) == self.hybrid_attn_every - 1


# ---------------------------------------------------------------------------
# tensor-parallel axis helpers
# ---------------------------------------------------------------------------

_TP_AXIS = "model"
_DP_AXES = ("pod", "data")

_state = threading.local()


def set_mesh_axes(tp_size: Optional[int]) -> None:
    """Record the tensor-parallel degree for divisibility-aware specs.
    ``None`` disables sharding decisions (single-device tests)."""
    _state.tp = tp_size


def tp_size() -> Optional[int]:
    return getattr(_state, "tp", None)


def shard_if_divisible(size: int) -> Optional[str]:
    tp = tp_size()
    if tp and size % tp == 0:
        return _TP_AXIS
    return None


def logical(x: jnp.ndarray, *axes: Optional[str]) -> jnp.ndarray:
    """Activation sharding constraint; no-op outside a mesh context."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    clean = []
    for a in axes:
        if a is None:
            clean.append(None)
        elif isinstance(a, str):
            clean.append(a if a in names else None)
        else:
            sub = tuple(s for s in a if s in names)
            clean.append(sub if sub else None)
    return jax.lax.with_sharding_constraint(x, P(*clean))


# ---------------------------------------------------------------------------
# initializers (params + spec twins)
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, *, out_shard: bool = True,
               in_shard: bool = False, bias: bool = False,
               scale: Optional[float] = None):
    """2D projection.  Returns (params, specs)."""
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype) * jnp.asarray(s, dtype)
    spec_in = shard_if_divisible(d_in) if in_shard else None
    spec_out = shard_if_divisible(d_out) if out_shard else None
    params = {"w": w}
    specs = {"w": P(spec_in, spec_out)}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
        specs["b"] = P(spec_out)
    return params, specs


def dense_apply(p, x):
    # Explicit accumulator dtype = activation dtype: GSPMD then
    # all-reduces TP matmul partials in bf16 instead of f32 (the MXU
    # still accumulates f32 internally per tile) -- halves TP wire bytes.
    y = jax.lax.dot_general(
        x, p["w"].astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def embed_init(key, vocab: int, d: int, dtype):
    w = jax.random.normal(key, (vocab, d), dtype) * 0.02
    return {"w": w}, {"w": P(shard_if_divisible(vocab), None)}


def rmsnorm_init(d: int, dtype):
    return {"g": jnp.ones((d,), dtype)}, {"g": P(None)}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def grad_dtype_boundary(x, dtype=None):
    """Identity in the forward pass; casts the COTANGENT to ``dtype``
    (default: x.dtype) in the backward pass.  Placed between the layer
    stack and the f32 loss head so backward TP all-reduces run in bf16
    (standard mixed-precision practice; halves backward wire bytes)."""
    dt = dtype or x.dtype

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, ct):
        return (ct.astype(dt),)

    f.defvjp(fwd, bwd)
    return f(x)


def activation(name: str):
    if name == "swiglu":
        return jax.nn.silu
    if name == "geglu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)
