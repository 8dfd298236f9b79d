"""The serving stack's logit oracle (ISSUE 31): serving logits — prefill
rows, decode rows, a chunk's next-token row — are compared with a
reference computed another way (the whole-sequence forward of the same
model, or the one-shot prefill for a chunked prompt) under ONE stated
tolerance, and must choose the same greedy token at every compared
position. Token-stream comparisons (async vs sync, recovered vs
uninterrupted, co-tenant vs solo, sharded vs single-shard, speculative vs
greedy, cached vs cold) do not come through here: they stay exact list
equality in their own tests."""
import numpy as np

#: The tolerance, in ulp of the reference's LARGEST logit (float32). In
#: ulp of the largest logit on purpose: a fixed absolute limit is vacuous
#: on the transformer decoder's tiny logits (1e-5 is 0.5% of them) and an
#: elementwise relative one is ill-conditioned at logits near zero.
#: Measured basis (CPU, f32, jax 0.9.0, PR 31), every array the tests
#: compare: 2.0-8.3 ulp — 1.07e-6 to 1.97e-6 (4.5-8.3 ulp) on the GPT-2
#: fixtures' logits of magnitude 2.5-3.3, 7.0e-10 to 1.2e-9 (5-6 ulp) on
#: the transformer decoder's of magnitude 0.002-0.003, 2 ulp on the
#: LSTM's. The limit is eight times the largest reading;
#: tests/test_serving.py's control shows what it refuses: a zeroed K row
#: reads 4,820 times the tolerance, a cursor one short 185,000 times, a
#: chunk written one position late 155,000 times.
LOGIT_ULP_LIMIT = 64


def logit_tolerance(ref, ulp_limit: int = LOGIT_ULP_LIMIT) -> float:
    """``ulp_limit`` float32 ulp of ``ref``'s largest magnitude."""
    top = np.float32(np.max(np.abs(np.asarray(ref, np.float32))))
    return float(ulp_limit * np.spacing(top))


def logit_gap(got, ref) -> float:
    """Largest absolute difference between two logit arrays."""
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    return float(np.max(np.abs(got.astype(np.float64)
                               - ref.astype(np.float64))))


def assert_matches_reference(got, ref, what: str = "logits",
                             ulp_limit: int = LOGIT_ULP_LIMIT) -> None:
    """``got`` (..., vocab) is within the tolerance of ``ref`` AND picks
    the same greedy token in every row. ``ulp_limit``: a model whose
    sound readings sit higher states its own limit with their basis
    (tests/test_jamba.py: a recurrence of exponentials eight layers
    deep)."""
    got, ref = np.asarray(got), np.asarray(ref)
    gap, tol = logit_gap(got, ref), logit_tolerance(ref, ulp_limit)
    assert gap <= tol, (
        f"{what}: differ from the reference by {gap:.3e}, "
        f"{gap / tol * ulp_limit:.1f} ulp of its largest logit "
        f"(limit {ulp_limit} ulp = {tol:.3e})")
    a, b = np.argmax(got, axis=-1), np.argmax(ref, axis=-1)
    assert np.array_equal(a, b), (
        f"{what}: greedy tokens differ from the reference's at "
        f"{np.argwhere(a != b).tolist()}")
