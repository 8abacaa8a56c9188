from .attention import (
    default_attention,
    normalize_segment_ids,
    segments_overlap,
    softclamp,
    MASK_VALUE,
    EPSILON,
    PAD_SEGMENT_ID,
    SegmentIds,
)
from .flash import (
    FlashCarry,
    attend_blocks,
    finalize,
    flash_attention,
    flash_backward_blocks,
    init_carry,
)
from .pallas_flash import (
    BandPlan,
    QuantizedKV,
    band_plan,
    pallas_flash_attention,
    pallas_flash_decode,
    pallas_flash_decode_q8,
    quantize_kv_cache,
)
from . import quant
from .quant import QuantizedBlockKV
from .rotary import apply_rotary, ring_positions, rotary_freqs
from .. import masks as _masks


def attention(
    q,
    k,
    v,
    mask=None,
    *,
    causal: bool = False,
    window: int | None = None,
    softclamp_value: float | None = None,
    impl: str = "auto",
    bucket_size: int | None = None,
    q_chunk_size: int | None = None,
    head_chunks: int | None = None,
    interpret: bool | None = None,
    segment_ids=None,
    doc_starts: tuple[int, ...] | None = None,
    compute_dtype: str | None = None,
):
    """Single-device attention entry point with graceful kernel degradation.

    ``mask`` accepts either a ``(b, nk)`` boolean key-padding array (the
    classic form) or a :class:`ring_attention_tpu.masks.Mask` algebra
    expression — ``attention(q, k, v, mask=Causal() & SlidingWindow(512))``.
    A mask expression is resolved onto the kernel knobs through
    ``masks.kernel_form`` (``causal=True`` elsewhere is sugar for
    ``Causal()``), its lowering is CERTIFIED at trace time
    (sound/tight/complete against the mask's own oracle —
    ``masks.require_certified``, cached next to the compile cache), and
    it subsumes ``causal=`` / ``window=`` / ``doc_starts=`` (passing
    both raises).  Expressions beyond the kernel surface (prefix-LM,
    dilated, per-head) raise :class:`~ring_attention_tpu.masks.
    MaskLoweringError` naming the supported forms.

    ``impl`` selects the kernel path:

    - ``"pallas"`` — the Mosaic kernels (:func:`pallas_flash_attention`);
      failures propagate (an explicit request must fail loudly).
    - ``"xla"`` — the pure-XLA flash path (:func:`flash_attention`).
    - ``"auto"`` (default) — try Pallas, FALL BACK to XLA when the Pallas
      path cannot compile/lower on this backend (missing plugin, Mosaic
      rejection, older jax).  The first failure emits one warning and is
      recorded in ``ring_attention_tpu.utils.resilience.degradation`` —
      queryable, so a run that silently lost its fast kernels is
      distinguishable from one that never had them.  Resolution happens at
      trace time (an outer ``jax.jit`` compiles exactly one path), backed
      by a tiny one-shot compile probe so the choice is made *before* a
      caller's multi-minute compile bakes it in.  On non-TPU backends
      ``auto`` takes XLA silently (no degradation record): interpret-mode
      Pallas would be a pessimization there, not a fallback.

    ``bucket_size``/``q_chunk_size`` apply to the XLA path,
    ``head_chunks``/``interpret``/``doc_starts`` to the Pallas path; both
    sets are legal with ``impl="auto"`` (whichever path runs uses its
    own).  ``segment_ids`` (packed sequences) applies to both.

    ``compute_dtype="int8"`` (quantized QK^T/PV, ``docs/precision.md``)
    exists only on the Pallas kernels, so it suspends the graceful
    degradation: ``impl="xla"`` raises, and ``"auto"`` requires the probe
    to resolve Pallas and lets kernel failures PROPAGATE — silently
    falling back to bf16 compute would misreport every number a
    quantized run exists to measure.
    """
    from ..utils import resilience
    from ..utils.validate import check_attention_args

    attn_mask = None
    if isinstance(mask, _masks.Mask):
        attn_mask, mask = mask, None  # the padding-mask slot stays empty

    # validate BEFORE any fallback machinery (or mask resolution, which
    # reads shapes): a caller's input error must raise as itself, never
    # be mistaken for a kernel failure and mark the Pallas path degraded
    # for the rest of the process
    check_attention_args("attention", q, k, v, mask)

    if attn_mask is not None:
        if causal or window is not None:
            raise ValueError(
                "attention: a mask expression subsumes causal=/window= — "
                "compose them into the mask (causal=True is sugar for "
                "Causal())"
            )
        form = _masks.kernel_form(attn_mask)  # raises MaskLoweringError
        causal, window = form.causal, form.window
        if form.doc_starts is not None:
            if doc_starts is not None:
                raise ValueError(
                    "attention: the mask already declares a DocumentMask "
                    "packing; drop the doc_starts= argument"
                )
            doc_starts = form.doc_starts
        if form.needs_segment_ids and segment_ids is None:
            raise ValueError(
                "attention: the mask includes Segments() — pass the "
                "runtime segment_ids array"
            )
        if q.shape[2] == k.shape[2]:
            # trace-time certificate for the grids this call lowers to,
            # cached by (mask, shape, blocks, strategy, layout); cross-
            # attention spans have no self-attention grid to certify
            _masks.require_certified(
                attn_mask, _masks.spec_for_call("single", n=q.shape[2])
            )

    if head_chunks is not None and head_chunks > 1:
        h, hk = q.shape[1], k.shape[1]
        if h % head_chunks or hk % head_chunks:
            raise ValueError(
                f"attention: head_chunks={head_chunks} must divide both "
                f"heads={h} and kv_heads={hk}"
            )

    # doc_starts is a SEMANTIC input (a declared packing layout), not a
    # perf knob: a path that cannot resolve it into kernel tables must
    # realize it as runtime segment ids, never silently drop it — the
    # XLA fallback would otherwise compute cross-document attention
    xla_segment_ids = segment_ids
    if doc_starts is not None and segment_ids is None:
        from .pallas_flash import _check_doc_starts, _doc_runtime_ids

        nq, nk = q.shape[2], k.shape[2]
        _check_doc_starts(doc_starts, nq, nk)
        xla_segment_ids = _doc_runtime_ids(doc_starts, nq, q.shape[0])

    def run_xla():
        return flash_attention(
            q, k, v, mask, causal=causal, window=window,
            softclamp_value=softclamp_value, bucket_size=bucket_size,
            q_chunk_size=q_chunk_size, segment_ids=xla_segment_ids,
        )

    def run_pallas():
        resilience.get_injector().check(resilience.PALLAS_FAULT)
        return pallas_flash_attention(
            q, k, v, mask, causal=causal, window=window,
            softclamp_value=softclamp_value, head_chunks=head_chunks,
            interpret=interpret, segment_ids=segment_ids,
            doc_starts=doc_starts, compute_dtype=compute_dtype,
        )

    resolved = resilience.resolve_attention_impl(impl)
    if compute_dtype is not None:
        if compute_dtype != "int8":
            raise ValueError(
                f"attention: compute_dtype={compute_dtype!r}; supported "
                'values are None and "int8"'
            )
        if resolved == "xla":
            raise ValueError(
                'attention: compute_dtype="int8" runs on the Pallas '
                f'kernels only, but impl={impl!r} resolved to the XLA '
                "path — a silent bf16 fallback would misreport a "
                "quantized run (docs/precision.md)"
            )
        return run_pallas()  # failures propagate: no bf16 degradation
    if resolved == "xla":
        return run_xla()
    if impl != "auto":
        return run_pallas()
    try:
        # probe passed, but this call's exact shape/config can still hit a
        # trace-time lowering failure — catch it and degrade rather than
        # kill a run the XLA path could have carried
        return run_pallas()
    except Exception as e:  # noqa: BLE001 — any Pallas failure degrades
        resilience.degradation.record(resilience.PALLAS_COMPONENT, e)
        return run_xla()


__all__ = [
    "attention",
    "normalize_segment_ids",
    "segments_overlap",
    "PAD_SEGMENT_ID",
    "SegmentIds",
    "BandPlan",
    "band_plan",
    "QuantizedKV",
    "QuantizedBlockKV",
    "quant",
    "pallas_flash_attention",
    "pallas_flash_decode",
    "pallas_flash_decode_q8",
    "quantize_kv_cache",
    "default_attention",
    "softclamp",
    "MASK_VALUE",
    "EPSILON",
    "FlashCarry",
    "attend_blocks",
    "finalize",
    "flash_attention",
    "flash_backward_blocks",
    "init_carry",
    "apply_rotary",
    "ring_positions",
    "rotary_freqs",
]
