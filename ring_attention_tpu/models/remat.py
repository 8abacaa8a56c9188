"""Named rematerialization policies for RingTransformer layers.

``remat=True`` trades recompute for activation memory; WHAT the remat is
allowed to keep is the policy, and the binary ``remat_policy in (None,
"save_attn")`` switch this module replaces could not express the choices
that matter at million-token context (LWM-lineage
``get_gradient_checkpoint_policy``, SNIPPETS.md [1]).  Each registry entry
maps a stable name to a ``jax.checkpoint_policies`` policy over the named
residuals this codebase tags:

- ``flash_out`` / ``flash_lse`` — each layer's attention output + the
  online-softmax log-sum-exp (tagged in ``parallel/ring.py`` /
  ``ops/flash.py`` / ``ops/pallas_flash.py``): saving them lets the
  backward skip re-running the O(n^2) ring scan for the cost of
  ``(b, n, dim)`` + f32 ``(b, h, n)`` per layer.
- ``ffn_in`` — the post-norm FeedForward input (tagged in
  ``models/layers.py``): saving it elides the RMSNorm recompute in the
  FFN backward for ``(b, n, dim)`` per layer; the ``mult*dim``
  intermediate is NEVER saveable by name — with ``ff_chunk_size`` it never
  exists at full sequence extent at all (docs/memory.md).

The table (policy -> what the backward recomputes):

=========================  ==============================================
``nothing_saveable``       everything (block inputs only — the default
                           ``remat=True`` behavior, maximum memory savings)
``everything_saveable``    nothing (remat becomes a no-op; A/B baseline)
``checkpoint_dots``        elementwise ops only (matmul outputs saved)
``checkpoint_dots_no_batch``  as above, skipping batched dots
``save_attn``              the FFN and the attention residual recompute,
                           but NOT the ring scan (flash_out/lse saved)
``save_ffn_inputs``        everything except the per-layer RMSNorm feeding
                           the FFN (ffn_in saved)
``save_attn_and_ffn_inputs``  union of the two named policies
``offload_attn``           as ``save_attn``, but the saved residuals live
                           in host memory (``pinned_host``) instead of
                           HBM; ``save_attn`` on a backend without an
                           addressable host space (see
                           ``utils/compat.host_memory_kind``)
=========================  ==============================================

Policies are per-layer selectable on ``RingTransformer`` (a tuple of names
mirrors ``max_lookback_seq_len``) and from ``examples/train.py
--remat-policy``; the recompute signature of each is HLO-pinned in
``tests/test_memory.py``.
"""

from __future__ import annotations

import jax

_ATTN_NAMES = ("flash_out", "flash_lse")
_FFN_NAMES = ("ffn_in",)


def _named(*names):
    return jax.checkpoint_policies.save_only_these_names(*names)


def _offload_attn():
    """save_attn with the residuals parked in host memory when the backend
    has one; plain save_attn otherwise (the graceful-degradation contract
    every compat shim here follows)."""
    from ..utils import compat

    kind = compat.host_memory_kind()
    if kind is None:
        return _named(*_ATTN_NAMES)
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=list(_ATTN_NAMES),
        offload_src="device",
        offload_dst=kind,
    )


# name -> zero-arg factory returning a jax.checkpoint policy.  Factories
# (not policy objects) because offload_attn probes the backend and the
# probe must not run at import time.
REMAT_POLICIES = {
    "nothing_saveable": lambda: jax.checkpoint_policies.nothing_saveable,
    "everything_saveable": lambda: jax.checkpoint_policies.everything_saveable,
    "checkpoint_dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "checkpoint_dots_no_batch": (
        lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    ),
    "save_attn": lambda: _named(*_ATTN_NAMES),
    "save_ffn_inputs": lambda: _named(*_FFN_NAMES),
    "save_attn_and_ffn_inputs": lambda: _named(*_ATTN_NAMES, *_FFN_NAMES),
    "offload_attn": _offload_attn,
}


def resolve_remat_policy(name: str | None):
    """Policy object for a registry name (None -> None, plain full-block
    remat).  Raises ``ValueError`` naming every valid policy — the
    validation the old ``assert`` version lost under ``python -O``."""
    if name is None:
        return None
    try:
        factory = REMAT_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {name!r}; valid policies: "
            f"{', '.join(sorted(REMAT_POLICIES))} (or None for plain "
            f"full-block remat)"
        ) from None
    return factory()
