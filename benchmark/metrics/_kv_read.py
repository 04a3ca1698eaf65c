"""Shared by the readers of a stack with full and window layers: the
``serve_kv_read`` events the engine stamps after each step of a model whose
cache is of several block groups.  A trace of a program without them gives
nothing to read: ``None``, and nothing raises."""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import common, program_trace


def events(ctx) -> Optional[List[Dict[str, int]]]:
    """The stats of the trace's ``serve_kv_read`` events."""
    pt = program_trace.load(ctx)
    found = [st for _, _, _, st in program_trace.spans(pt, "serve_kv_read")]
    found = [e for e in found if "full_keys" in e and "window_keys" in e]
    if not found:
        return None
    common.say(f"serve_kv_read: {len(found)} events, "
               f"{sum(int(e['full_keys']) for e in found)} keys a full layer "
               f"read, {sum(int(e['window_keys']) for e in found)} a window "
               "layer")
    return found
