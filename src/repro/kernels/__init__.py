"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

  chacha20/  CTR keystream generation + XOR — the boundary-crossing tax the
             paper pays on every enclave exit (AES-NI there, VPU ARX here).
  kmeans/    fused assign+accumulate for the paper's evaluation workload
             (map step: n×k distances on the MXU, argmin, per-center sums).

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). Tests sweep shapes/dtypes and
assert_allclose against ref.

Every kernel entry point takes `interpret=None`, resolved by
`resolve_interpret`: compiled on the TPU, interpreted on any other backend.
"""

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    None follows the default backend — compiled on the TPU, interpreted
    everywhere else (the CPU test backend has no Mosaic lowering); an
    explicit bool is taken as given. Read at trace time.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
