"""Pallas TPU kernels for the hot ops.

The reference has no custom kernels (its compute path is TF's, SURVEY.md
§2) — but the TPU build's perf ceiling is set by how well the hot loop
maps onto the MXU/VMEM, so the ops that XLA cannot fuse optimally are
hand-written here with Pallas:

- ``attention`` — blocked flash attention (fwd + bwd) with online
  softmax: O(seq) memory, never materializes the (seq, seq) score
  matrix in HBM; sliding-window variants skip out-of-window tiles.
- ``decode_attention`` — one near-bandwidth HBM pass over a
  fixed-capacity KV cache for autoregressive decoding, with optional
  int8 dequantization in VMEM (``quantize_kv``) and native GQA
  query-head grouping.
- ``chunked_softmax_xent`` — LM-head loss computed per sequence chunk
  under ``jax.checkpoint``: the (batch, seq, vocab) fp32 logits are
  never materialized (peak chunk x vocab instead).
- ``gated_delta_rule`` — the recurrence of a Gated-DeltaNet
  linear-attention layer in chunks of 64 tokens, with a backward pass of
  its own; on a TPU five Pallas kernels (``gated_delta_local_fwd``,
  ``gated_delta_fwd``, ``gated_delta_out_fwd``, ``gated_delta_bwd``,
  ``gated_delta_local_bwd``) keep a chunk's matrices and the state in VMEM.
- ``kda_rule`` — the delta rule with a decay per key channel (Kimi Delta
  Attention) in chunks of 64 tokens, on a layer's own arrays ((b, s, h, d),
  ``q`` and ``k`` before their L2 norm, the log-decay and not its running
  sum), with a backward pass written by hand; on a TPU two Pallas kernels
  (``kda_fwd``, ``kda_bwd``: they read (chunk, heads x d) tiles of those
  arrays and write the cotangents the same way, ``o`` head-major as the
  gated norm after it is laid out; the norms, the running sum, a chunk's
  matrices, the decayed operands and the state stay in VMEM).
- ``selective_scan`` — the recurrence of a Mamba layer in chunks, with a
  backward pass of its own that recomputes a chunk's states; on a TPU two
  Pallas kernels (``selective_scan_fwd``, ``selective_scan_bwd``) keep the
  state in VMEM. No array of per-token states is made.

Every kernel ships with a pure-XLA reference twin used for (a) numeric
tests, (b) non-TPU backends, (c) shapes the kernel doesn't support.
"""

from hops_tpu import _startup

with _startup.importing("hops_tpu.ops"):
    from hops_tpu.ops.attention import (  # noqa: F401
        attention_reference,
        decode_attention,
        decode_attention_q8,
        decode_attention_reference,
        dequantize_kv,
        flash_attention,
        paged_decode_attention,
        paged_decode_attention_reference,
        paged_gather_kv,
        quantize_kv,
        repeat_kv,
    )
    from hops_tpu.ops.gated_delta import gated_delta_rule  # noqa: F401
    from hops_tpu.ops.kda import kda_rule  # noqa: F401
    from hops_tpu.ops.xent import chunked_softmax_xent  # noqa: F401
