"""Executable census for the compiled search steps.

The policy-as-tensor TTA design promises ONE executable per argument
shape for the whole search (SURVEY.md hard-part 3); the census is how
the driver PROVES it in every `search_result.json` instead of claiming
it, and a count above the expected one fails the search.
"""

from __future__ import annotations

__all__ = ["executable_census"]


def executable_census(step) -> int:
    """Compiled executables held by a jitted step: the size of jit's
    own cache (``_cache_size``, which the seam wrapper delegates)."""
    return int(step._cache_size())
