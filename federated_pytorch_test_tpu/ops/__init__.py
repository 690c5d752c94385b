"""TPU kernel ops (Pallas).

Hand-written Pallas kernels for the framework's hot ops, with XLA fallbacks
so every op runs identically on CPU/interpret mode.  Currently:

  * :func:`info_nce_fused` — fused InfoNCE (CPC contrastive loss): Gram
    matmul + normalisation + online log-softmax + diagonal gather in one
    VMEM-resident kernel.
  * ``comm_kernels`` — the packed collective's ``quantize_chunks``,
    ``dequant_add`` and ``gram_matrix``; ``topk_select`` — two-stage top-k.
  * ``gated_delta.gated_delta_chunked`` — the chunked gated delta rule;
    its recurrence over the chunks is a forward and a backward kernel
    under one ``custom_vjp`` that keep the state ``S`` in VMEM
    (``gated_delta.force_gdn_scan_impl`` for tests); the triangular
    inverse of its chunk-local part is differentiated by the closed form
    ``-T^T dT T^T`` on every backend.
  * ``flash_attention.causal_attention`` — causal softmax attention with
    grouped query heads, blockwise with an online softmax: a forward and
    a backward kernel under one ``custom_vjp`` that keep the score tiles
    in VMEM and skip the masked half
    (``flash_attention.force_attn_impl`` for tests).
  * ``hyper_connections.pre`` / ``expand`` — the two halves of a
    sub-layer under manifold-constrained hyper-connections (streams
    stream-major, the maps' tokens along the lanes): on a TPU each is one
    ``custom_vjp`` whose passes over the float32 streams are kernels that
    read them once and write their result once (norm, projection,
    ``H_pre X``; the mixing; both transposes, the streams' cotangent
    written once); the sigmoids and the Sinkhorn projection stay
    ``jax.numpy`` (``hyper_connections.force_mhc_impl`` for tests).
  * ``head_loss.head_loss`` — no kernel of its own: a decoder head's
    cross-entropy under one ``custom_vjp`` whose forward rule takes the
    gradient of its inputs from the logits it made, so the head's
    product is never rematerialised; plain JAX on every backend.
  * ``moe`` — no kernel of its own: the two router rules
    (``router_weights``, ``sigmoid_router_weights``) and the held
    experts' sorted pairs and grouped products (``jax.lax.ragged_dot``,
    which the TPU compiler turns into a grouped Mosaic kernel).
"""

from federated_pytorch_test_tpu.ops.infonce import (  # noqa: F401
    force_infonce_impl,
    info_nce_fused,
)

__all__ = ["info_nce_fused", "force_infonce_impl"]
