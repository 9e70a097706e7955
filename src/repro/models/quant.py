"""Weight-only int8 quantization for serving.

Production motivation (EXPERIMENTS §Perf, mistral-large prefill hillclimb):
2-D-sharded (FSDP×TP) weights make *serving* collective-bound — every
prefill/decode step all-gathers each layer's weights over the ``data``
axis.  Dropping FSDP (TP-only residency) removes those collectives but a
123B bf16 model doesn't fit 16-way TP on v5e (15.4 GiB/chip of weights
alone).  Weight-only int8 (per-output-channel scales) halves that to
7.7 GiB — collective-free serving that fits, at ~0.5 bit/weight quality
cost (standard W8A16: matmuls still run in bf16 after dequant).

``QuantizedTensor`` is a pytree node, so spec trees / shardings / jit all
treat it transparently; ``deq()`` at the use site is the only model-code
touch point.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import Spec, is_spec
from repro.sharding.logical import axes_to_sharding


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    q: Any       # int8 payload, same logical shape as the original weight
    scale: Any   # fp32, shape = original with quantized axis reduced to 1

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape


def deq(w, dtype=None):
    """Dequantize if quantized; identity otherwise (model-code shim).

    ``dtype`` is the *activation* dtype of the consuming matmul — every
    model call site passes it (``deq(p["wq"], xn.dtype)``) so W8A16
    matmuls run in whatever precision the activations carry.  With no
    dtype the scales' own (fp32) precision is kept: the old hardcoded
    ``bfloat16`` default silently downcast fp32-activation engines when
    a call site forgot the argument.
    """
    if isinstance(w, QuantizedTensor):
        if dtype is None:
            dtype = w.scale.dtype
        return (w.q.astype(dtype) * w.scale.astype(dtype))
    return w


def quantize(w: jax.Array, keep_leading: bool = False) -> QuantizedTensor:
    """Per-last-axis-channel symmetric int8 quantization.

    ``keep_leading`` preserves axis 0 (scan-stacked layer dim) so every
    layer gets its own scales and the tree stays scannable.
    """
    start = 1 if keep_leading else 0
    reduce_axes = tuple(range(start, w.ndim - 1))
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale)


def _quantizable(spec: Spec) -> bool:
    """Quantize matmul weights (≥2-D plain-init); embeddings/unembeddings,
    routers (scaled init), norms, biases and conv taps stay bf16."""
    return len(spec.shape) >= 2 and spec.init == "normal" and spec.scale is None


def _scale_layout(spec: Spec) -> Tuple[Tuple[int, ...], Tuple[Optional[str], ...]]:
    """Shape + logical storage axes of a quantizable spec's scale tensor
    (all-but-last axes reduced to 1; the leading scan-stacked layer dim,
    if any, keeps per-layer scales)."""
    lead = 1 if spec.axes[0] == "layers" else 0
    shape = tuple(
        list(spec.shape[:lead])
        + [1] * (len(spec.shape) - 1 - lead)
        + [spec.shape[-1]]
    )
    axes = tuple(
        list(spec.fsdp_axes()[:lead])
        + [None] * (len(spec.shape) - 1 - lead)
        + [spec.fsdp_axes()[-1]]
    )
    return shape, axes


def quantize_params(params, specs) -> Any:
    """Real-array quantization (serving engines with materialized weights).

    Idempotent: already-quantized leaves pass through, so a cluster can
    hand the same tree to several engine replicas that each default
    ``REPRO_QUANT=1`` without double-quantizing.
    """
    return jax.tree.map(
        lambda p, s: (
            quantize(p, keep_leading=s.axes[0] == "layers")
            if _quantizable(s) and not isinstance(p, QuantizedTensor) else p
        ),
        params, specs,
        is_leaf=lambda x: is_spec(x) or isinstance(x, QuantizedTensor),
    )


def serving_param_shardings(params, specs, mesh, rules=None):
    """NamedSharding tree matching ``params`` (quantized or not) for
    placing one replica's weights onto its serving mesh.

    Mirrors :func:`repro.models.params.param_shardings` but follows the
    *materialized* tree: a ``QuantizedTensor`` leaf gets a
    ``QuantizedTensor(q_sharding, scale_sharding)`` node so
    ``jax.device_put(params, shardings)`` maps leaf-for-leaf.  On a
    TP-only serving mesh the FSDP axis (``embed_fsdp → "data"``) doesn't
    exist, so embeddings/norms replicate and matmul weights shard on
    ``"model"`` — collective-free residency.
    """

    def mk(p, s):
        w_sh = axes_to_sharding(s.fsdp_axes(), mesh, rules, shape=s.shape)
        if isinstance(p, QuantizedTensor):
            scale_shape, scale_axes = _scale_layout(s)
            return QuantizedTensor(
                q=w_sh,
                scale=axes_to_sharding(scale_axes, mesh, rules,
                                       shape=scale_shape),
            )
        return w_sh

    return jax.tree.map(
        mk, params, specs,
        is_leaf=lambda x: is_spec(x) or isinstance(x, QuantizedTensor),
    )


def abstract_quantized_params(
    specs, mesh=None, rules=None, dtype=jnp.bfloat16
):
    """ShapeDtypeStruct tree with int8 payloads — dry-run stand-ins."""

    def mk(spec: Spec):
        if mesh is not None:
            sharding = axes_to_sharding(spec.fsdp_axes(), mesh, rules,
                                        shape=spec.shape)
        else:
            sharding = None
        if not _quantizable(spec):
            return jax.ShapeDtypeStruct(spec.shape, dtype, sharding=sharding)
        scale_shape, scale_axes = _scale_layout(spec)
        scale_sh = None
        if mesh is not None:
            scale_sh = axes_to_sharding(scale_axes, mesh, rules,
                                        shape=scale_shape)
        return QuantizedTensor(
            q=jax.ShapeDtypeStruct(spec.shape, jnp.int8, sharding=sharding),
            scale=jax.ShapeDtypeStruct(scale_shape, jnp.float32,
                                       sharding=scale_sh),
        )

    return jax.tree.map(mk, specs, is_leaf=is_spec)


def shard_residency_bytes(
    specs, *, tp: int, rules=None, quant: bool = True, dtype=jnp.bfloat16,
) -> int:
    """Per-shard weight-residency bytes of one TP shard — the number a
    chip's HBM budget is checked against (DESIGN.md §15).

    Built over a ``jax.sharding.AbstractMesh`` with a single ``tp``-wide
    ``"model"`` axis, so it needs **zero** devices (the large-config smoke
    test and the ``tp_serving`` benchmark both run it on a 1-CPU
    container).  Sums each leaf's ``sharding.shard_shape`` bytes — the
    same divisibility-aware resolution the real serving mesh uses, so a
    dim the axis can't tile is honestly counted as replicated.
    """
    from repro.models.params import abstract_params

    mesh = jax.sharding.AbstractMesh((int(tp),), ("model",))
    tree = (abstract_quantized_params(specs, mesh, rules, dtype=dtype)
            if quant else abstract_params(specs, dtype, mesh, rules))
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = (leaf.sharding.shard_shape(leaf.shape)
                 if leaf.sharding is not None else leaf.shape)
        total += int(np.prod(shape, dtype=np.int64)) * jnp.dtype(leaf.dtype).itemsize
    return total
