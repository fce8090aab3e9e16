"""Pallas TPU kernels for the compute hot-spots (see DESIGN.md §2):

  route_accumulate -- PE buffer scatter-accumulate as a one-hot VPU reduce
  cms_update       -- count-min sketch multi-row update (one-hot MXU)
  moe_onehot       -- dispatch/combine one-hot contractions (routing network)
  flash_attention  -- online-softmax attention fwd (LM prefill hot-spot)

dispatch.py is the backend-dispatch layer: every kernel has jnp-reference,
Pallas-interpret, and Pallas-native realizations, selected per
``jax.default_backend()`` with explicit overrides.  ops.py holds the public
wrappers (all routed through dispatch); ref.py the pure-jnp oracles.
"""
from repro.kernels import dispatch, ops, ref

__all__ = ["dispatch", "ops", "ref"]
