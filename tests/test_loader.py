"""Data pipeline: prefetch loader order + liveness."""
import numpy as np

from repro.data.loader import PrefetchLoader


def _numbered(i: int) -> dict:
    return {"tokens": np.full((2, 4), i, np.int32)}


def test_prefetch_loader_streams():
    loader = PrefetchLoader(_numbered, depth=2)
    try:
        seen = [next(loader) for _ in range(5)]
        assert len(seen) == 5
        # prefetch preserves order
        for i, batch in enumerate(seen):
            np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                          _numbered(i)["tokens"])
    finally:
        loader.close()
