"""Stable compaction of a padded output's valid rows to a static width.

An NFA step emits ``[B, slots + 1]`` flattened: a column is thirty-odd
times the batch wide and a few percent of it are matches, and the host
pull pays for the bytes (1.3 ms plus 1.9 ms per 65,536 rows a pull on the
v5e: PERF.md). ``compact_columns`` builds ``[C]``-wide twins of every
column that hold the valid rows first, in index order. They are what the
step delivers; the padded columns ride beside them, and the host pulls
those instead whenever the meta's count does not fit ``C``
(``LazyColumns.choose``), so no row is ever dropped.

No operation here is as wide as the padded output except one pass that
packs the valid mask into 32-bit words; everything else is as wide as the
words or as ``C``. Columns are moved by one gather each and never computed
on: a double keeps its bits (on the TPU it is a pair of float32 with no
bits to take: PERF.md, PR 28).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
from jax import lax

from siddhi_tpu.ops.expressions import VALID_KEY

_WORD = 32


def compact_width(batch_rows: int, padded_rows: int) -> Optional[int]:
    """The compacted width for a step whose input batch holds ``batch_rows``
    and whose output columns are ``padded_rows`` long: the least power of
    two at or above twice the batch (a match is caused by one arriving
    row; more than one a row is the rare case), or None where the padded
    output is not at least six times that and compacting buys nothing.

    The six, from the two lines on record (PERF.md section 6, PR 30): the
    compaction is its gathers, 3.8 ms for 32,768 rows of the benchmark's
    pattern columns, 0.119 ms per 1,024 rows of the width; a pull costs 1.9
    ms per 65,536 rows, 0.030 ms per 1,024 rows not pulled. They are even
    at a padded output five times the width, and six leaves a fifth of the
    saving as gain; with the default ``nfa_slots`` (32) the ratio is 16.5.
    Only that shape was measured on the chip; a power-of-two batch with
    ``nfa_slots`` below 11 keeps the padded pull."""
    width = 1 << (2 * batch_rows - 1).bit_length()
    return width if 6 * width <= padded_rows else None


def valid_row_indices(valid, width: int):
    """``[width]`` int32: the index of the i-th set row of ``valid`` at
    position i (index order, so the compaction is stable), 0 at and beyond
    the number of set rows. Exact while that number is at most ``width``.

    Two levels: the mask as 32-bit words, a cumulative count over the
    words, the word of each output position by one scatter of the words'
    first positions and a running maximum, then the bit within the word by
    rank (population counts of the word's prefixes)."""
    n = valid.shape[0]
    n_words = -(-n // _WORD)
    lanes = jnp.arange(_WORD, dtype=jnp.uint32)
    bits = jnp.pad(valid, (0, n_words * _WORD - n)).reshape(n_words, _WORD)
    words = jnp.sum(jnp.where(bits, jnp.uint32(1) << lanes, jnp.uint32(0)),
                    axis=1, dtype=jnp.uint32)
    per_word = lax.population_count(words).astype(jnp.int32)
    before = jnp.cumsum(per_word, dtype=jnp.int32) - per_word
    # a word with no set row, or past the width, lands out of range: dropped
    first = jnp.where(per_word > 0, before, width)
    word_of = lax.cummax(jnp.zeros(width, jnp.int32).at[first].set(
        jnp.arange(n_words, dtype=jnp.int32), mode="drop"), axis=0)
    pos = jnp.arange(width, dtype=jnp.int32)
    rank = pos - before[word_of]
    # set rows of the word at or below each lane; the lanes that hold at
    # most `rank` of them are those before the wanted row
    upto = lax.population_count(
        words[word_of][:, None] & ((jnp.uint32(2) << lanes) - jnp.uint32(1)))
    lane = jnp.sum(upto.astype(jnp.int32) <= rank[:, None], axis=1,
                   dtype=jnp.int32)
    count = before[-1] + per_word[-1]
    return jnp.where(pos < count, word_of * _WORD + lane, 0)


def compact_columns(out: Dict[str, object], width: int) -> Dict[str, object]:
    """``[width]``-wide twins of every column of ``out`` (the caller hands
    in only entries as long as its ``__valid__``): the valid rows first, in
    index order, and ``__valid__`` = the first ``count`` positions. Where
    no row is valid, or more than ``width`` are, the gathers are skipped
    and the twins hold zeros: the host pulls nothing, or the padded
    columns."""
    valid = out[VALID_KEY]
    count = jnp.sum(valid, dtype=jnp.int32)
    cols = {k: v for k, v in out.items() if k != VALID_KEY}

    def gather(operands):
        mask, columns = operands
        idx = valid_row_indices(mask, width)
        return {k: jnp.take(v, idx, axis=0) for k, v in columns.items()}

    def skip(operands):
        _mask, columns = operands
        return {k: jnp.zeros((width,) + v.shape[1:], v.dtype)
                for k, v in columns.items()}

    twins = lax.cond((count > 0) & (count <= width), gather, skip,
                     (valid, cols))
    twins[VALID_KEY] = jnp.arange(width, dtype=jnp.int32) < count
    return twins
