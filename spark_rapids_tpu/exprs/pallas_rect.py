"""Pallas TPU kernels for byte-rectangle string matching.

First custom-kernel tier below the XLA ops (SURVEY.md L0; the analog of
the reference's hand-written cudf string kernels, stringFunctions.scala
device paths). The sliding-pattern match family (contains / startswith /
endswith / locate) maps exactly onto the VPU: a byte rectangle
``bytes_[P, W]`` tiles as (rows, lanes); each pattern offset is a STATIC
lane slice compared against broadcast pattern constants, and the
first-match position is one lane-dim min-reduction. No gathers, no
scatters, no sorts — the kernel is pure elementwise + reduction work the
Mosaic compiler schedules tightly.

Opt-in via ``spark.rapids.tpu.sql.pallas.enabled`` (the XLA fallback in
string_rect.py stays the default until the kernel measures faster on
the target backend); on the CPU backend the kernels run in interpreter
mode so differential tests cover them everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..config import register

__all__ = ["PALLAS_ENABLED", "pallas_enabled", "pallas_match",
           "pallas_available"]

PALLAS_ENABLED = register(
    "spark.rapids.tpu.sql.pallas.enabled", False,
    "Route byte-rectangle string predicate kernels (contains/startswith/"
    "endswith/locate and the literal LIKE forms) through hand-written "
    "Pallas TPU kernels instead of the fused XLA ops "
    "(exprs/pallas_rect.py). On the CPU backend the kernels run in "
    "interpreter mode (tests). On a TPU backend enabling this FAILS: "
    "the chip's compiler refuses the kernel as written (see "
    "TPU_LOWERING_REFUSAL in exprs/pallas_rect.py). OFF by default.")

#: what the TPU's compiler said when ``_match_kernel`` was lowered with
#: ``interpret=False`` for a described v5e (jax 0.9.0, libtpu 0.0.34; PR
#: 21, widths 32 and 128, 4096 rows). The kernel had only ever run
#: interpreted.
TPU_LOWERING_REFUSAL = (
    "spark.rapids.tpu.sql.pallas.enabled cannot be used on a TPU backend: "
    "the chip's compiler refuses the byte-rectangle match kernel "
    "(exprs/pallas_rect.py). Lowered for a v5e with interpret=False, "
    "contains/locate raise 'RecursionError: maximum recursion depth "
    "exceeded', and startswith raises 'MosaicError: INTERNAL: Mosaic "
    "failed to compile TPU kernel: Target does not support this "
    "comparison' at arith.cmpi over vector<8x128x4xi8> (the per-byte "
    "uint8 lane compare). Leave the conf off — the fused XLA ops are the "
    "default — until the kernel is rewritten (ROADMAP Design 4).")

#: rows per grid step: uint8 tiles want >= 32 sublanes; 256 rows keeps
#: each block's VMEM footprint at 256*W bytes (W <= 1024)
_BLOCK_ROWS = 256


def pallas_available() -> bool:
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except ImportError:  # pragma: no cover - pallas ships with jax
        return False


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pallas_enabled(conf) -> bool:
    """The conf, read where the exec decides its string path. On a TPU
    backend a True value raises with the compiler's own words instead of
    letting the query die later in a raw lowering error."""
    on = bool(conf.get(PALLAS_ENABLED))
    if on and not _interpret():
        raise NotImplementedError(TPU_LOWERING_REFUSAL)
    return on


@functools.lru_cache(maxsize=None)
def _match_kernel(pat: bytes, mode: str, w: int, padded: int):
    """Build the pallas_call for one (pattern, mode, width, rows) shape.

    mode: "contains" | "startswith" | "endswith" | "equals" -> bool[P]
          "locate" -> int32[P] (1-based first occurrence, 0 if absent)
    """
    from jax.experimental import pallas as pl

    p = np.frombuffer(pat, np.uint8)
    L = len(p)
    grid = (padded // _BLOCK_ROWS,)
    out_dtype = jnp.int32 if mode == "locate" else jnp.bool_

    def kernel(b_ref, len_ref, out_ref):
        b = b_ref[...]                      # [BLOCK, W] uint8
        ln = len_ref[...]                   # [BLOCK] int32

        def match_at(s):
            # all pattern bytes match at static offset s
            m = jnp.ones((_BLOCK_ROWS,), jnp.bool_)
            for j, ch in enumerate(p):
                m = jnp.logical_and(m, b[:, s + j] == np.uint8(ch))
            return m

        if L == 0:
            # empty pattern: everything contains/starts/ends with it,
            # locate('')==1, but equals matches only empty strings
            if mode == "equals":
                out_ref[...] = ln == 0
            elif mode == "locate":
                out_ref[...] = jnp.ones((_BLOCK_ROWS,), jnp.int32)
            else:
                out_ref[...] = jnp.ones((_BLOCK_ROWS,), jnp.bool_)
            return
        if L > w:
            # pattern wider than the rectangle: no row can match
            out_ref[...] = (jnp.zeros((_BLOCK_ROWS,), jnp.int32)
                            if mode == "locate"
                            else jnp.zeros((_BLOCK_ROWS,), jnp.bool_))
            return
        if mode == "startswith":
            out_ref[...] = jnp.logical_and(ln >= L, match_at(0))
            return
        if mode == "equals":
            out_ref[...] = jnp.logical_and(ln == L, match_at(0))
            return
        if mode == "endswith":
            hit = jnp.zeros((_BLOCK_ROWS,), jnp.bool_)
            for s in range(w - L + 1):
                hit = jnp.where(ln - L == s, match_at(s), hit)
            out_ref[...] = jnp.logical_and(ln >= L, hit)
            return
        # contains / locate: first offset whose window matches
        first = jnp.full((_BLOCK_ROWS,), w + 1, jnp.int32)
        for s in range(w - L + 1):
            m = jnp.logical_and(match_at(s), ln - L >= s)
            first = jnp.minimum(first,
                                jnp.where(m, jnp.int32(s + 1), w + 1))
        if mode == "locate":
            out_ref[...] = jnp.where(first <= w, first, 0)
        else:
            out_ref[...] = first <= w

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, w), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), out_dtype),
        interpret=_interpret(),
    )


def pallas_match(bytes_, lengths, pattern: bytes, mode: str):
    """Sliding-pattern match over a byte rectangle via the Pallas kernel.
    Traced (callable inside jit); pads rows to the block multiple and
    slices back."""
    padded, w = bytes_.shape
    rows = padded
    pad_to = -padded % _BLOCK_ROWS
    if pad_to:
        bytes_ = jnp.pad(bytes_, ((0, pad_to), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad_to))
        padded += pad_to
    out = _match_kernel(pattern, mode, w, padded)(
        bytes_, lengths.astype(jnp.int32))
    return out[:rows]
