"""Pallas TPU kernels of the LM and recsys stack (no GNN cell runs them).

Each subpackage ships: <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper + format helpers), ref.py (pure-jnp oracle).
Validated in interpret mode on CPU; the TPU target is v5e.

- embedding_bag:    recsys gather-reduce with scalar-prefetched row DMAs;
                    the kernel form of ``models/recsys/two_tower.embedding_bag``
- flash_attention:  online-softmax attention (GQA + sliding window); the
                    kernel form of ``models/lm/attention.chunked_attention``

No model module imports them: the models run the pure-JAX forms, and
``tests/test_kernels.py`` checks each kernel against its oracle and the
model function it mirrors (``tests/test_lm.py`` uses ``attention_ref``).
The SSO engine's gather and scatter are host numpy and plain XLA layer
programs (``runtime/forward.py``, ``core/engine.py``).
"""
