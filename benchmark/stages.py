"""The program's stage table (``ec_codec.stages`` of ``/status``: per span
name ``n``, ``busy_s`` and, where the stage carries them, ``bytes``,
``failed``, ``slept_s``) as the readers see it: the window's delta of two
snapshots. A daemon that serves no table (the parent of the PR that brought
it, ``SWEED_TRACE=0``) and a stage that never ran give None, and the reader
leaves its metric out."""

from __future__ import annotations


def delta(ctx: dict, stage: str, field: str):
    """after - before of one field of one stage, or None."""
    status = ctx.get("status") or {}
    before = (status.get("before") or {}).get("stages")
    after = (status.get("after") or {}).get("stages")
    if before is None or after is None or stage not in after:
        return None
    return after[stage].get(field, 0) - before.get(stage, {}).get(field, 0)


def ratio(ctx: dict, over: tuple[str, str], under: tuple[str, str],
          scale: float = 1.0):
    """scale x delta(over) / delta(under), each a (stage, field); None where
    either is missing or nothing of ``under`` happened in the window."""
    num, den = delta(ctx, *over), delta(ctx, *under)
    if num is None or not den:
        return None
    return scale * num / den
