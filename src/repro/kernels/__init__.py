"""TPU Pallas kernels for the compute hot-spots, with jnp fallbacks.

Layout (one module per kernel + shared dispatch/oracle):

  flash_attention.py : blockwise causal/bidirectional attention (MXU-tiled,
                       VMEM-resident online softmax)
  decode_attention.py: flash-decode — one query vs a long KV cache, KV-
                       partitioned partial softmax + combine
  ssm_scan.py        : RWKV-6 chunked linear-attention scan
  moe_gemm.py        : per-expert batched GEMM
  ragged_attention.py: ragged paged attention — the unified serving step's
                       one mixed prefill+decode dispatch
  ops.py             : public dispatch API (direct / flash / pallas / gather)
  ref.py             : pure-jnp oracles every kernel is validated against
  flash_jnp.py       : scan-based blockwise attention with custom VJP (the
                       CPU/dry-run path; same block structure as the Pallas
                       kernel)

On TPU every ``pl.pallas_call`` lowers to Mosaic and runs compiled; the
serving path picks them there by itself (``ModelContext.paged_kernel``).
On the CPU the tests run them with ``interpret=True`` against the oracles,
and ``tests/test_tpu_compile.py`` compiles the main-path kernels for a
described v5e chip.
"""
