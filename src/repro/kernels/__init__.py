"""Pallas TPU kernels for the paper's compute hot-spots, each with a
pure-jnp oracle (ref.py) and a jit'd public wrapper (ops.py):

  grouped_gemm.py       MXU-tiled grouped GEMM over ragged expert groups —
                        the paper's central operator (Fig. 3); visit-steered
                        grid handles mid-tile group boundaries without
                        padding compute; optional int8 weight-only path.
  splitkv_attention.py  flash-decode attention (one token vs a long KV
                        cache), online softmax + LSE output for the
                        cross-shard split-KV combine.
  flash_prefill.py      tiled online-softmax prefill attention with
                        causal / sliding-window / bidirectional masks.

Off the TPU the kernels run in the Pallas interpreter, on the TPU they
compile with Mosaic (backend.interpret_mode). They are validated against
their oracles in interpret mode on CPU, compiled for a described TPU v5e
at published widths (tests/test_tpu_compile.py), and checked against the
oracles on the chip by chip_smoke.py.
"""
