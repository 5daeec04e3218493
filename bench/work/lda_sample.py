"""Operations and bytes of one LDA training iteration, counted from the
corpus and the model state, never from the implementation's shapes.

The sampler is counted by the paper's Table 1 (section 3.1; int and float
4 bytes, theta sparse with K_d non-zeros):

    compute_S   4 K_d ops   3 * 4 K_d bytes         per real token
    sample_p1   6 K_d ops   (3 * 4 + 2 * 4) K_d     per real token
    compute_Q   2 K ops     2 * 4 K bytes           per distinct word
    sample_p2   3 K ops     (2 * 4 + 2 * 4) K       per distinct word

K_d is the number of live topics of the token's document, so the count
depends on the data alone: padding, ELL width and tiling add nothing, and a
change that drops work the model does not need reads as faster.  The dense
side (Q and the index tree over p*) is shared by every token of a word, so
it is counted once per distinct word.

The plan and the count update are counted the same way: the plan reads
each real token's document and topic (2 x 4 bytes) and writes each
document's sparse row (2 x 4 bytes per live topic); the count update reads
each real token's old and new topic and word (3 x 4 bytes) and adds one to
and takes one from a count (2 ops, 2 x 4 bytes written).
"""
from __future__ import annotations

INT = FLT = 4


def sampler(kd_sum: int, distinct_words: int, num_topics: int):
    """(ops, bytes) of one sweep.  ``kd_sum`` is the sum over real tokens
    of their document's live-topic count."""
    K = num_topics
    ops = (4 + 6) * kd_sum + (2 + 3) * K * distinct_words
    byts = (3 * INT + (3 * INT + 2 * FLT)) * kd_sum \
        + (2 * INT + (2 * INT + 2 * FLT)) * K * distinct_words
    return ops, byts


def plan(num_tokens: int, kd_docs_sum: int):
    """(ops, bytes) of rebuilding the sparse doc-topic rows from the
    assignments; ``kd_docs_sum`` is the sum over documents of K_d."""
    return num_tokens, 2 * INT * num_tokens + 2 * INT * kd_docs_sum


def count_update(num_tokens: int):
    """(ops, bytes) of advancing the topic-word counts by one sweep."""
    return 2 * num_tokens, 3 * INT * num_tokens + 2 * INT * num_tokens


def bound_seconds(ops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peaks["flops_per_s"], byts / peaks["hbm_bytes_per_s"])
