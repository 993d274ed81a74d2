"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three files:
  kernel.py -- ``pl.pallas_call`` body with explicit BlockSpec VMEM tiling
               (TPU is the target; CPU validation runs interpret=True),
  ops.py    -- the jit'd public wrapper (custom_vjp where training needs
               gradients; backward routes through the jnp oracle),
  ref.py    -- the pure-jnp oracle used by the allclose test sweeps.

Kernels:
  flash_attention -- causal GQA flash attention w/ sliding window
  ssd_scan        -- Mamba-2 chunked SSD (intra-chunk MXU matmuls,
                     sequential inter-chunk state carry)
  kmeans_assign   -- K-means E-step (the paper's own workload hot spot)
"""


def interpret_default() -> bool:
    """Native on TPU, the interpreter on the CPU (the test backend).

    Any other backend is an error: a kernel quietly interpreted on an
    accelerator would hide the device from every measurement."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels here target TPU (native) or CPU "
                       f"(interpret mode); backend {backend!r} is neither")
