"""The single-step TM classify function closed over a frozen servable."""

from __future__ import annotations

import functools
from typing import Optional

__all__ = ["make_tm_serve_fn"]


def make_tm_serve_fn(servable, path: Optional[str] = None):
    """Jitted TM classify step closed over a frozen :class:`ServableModel`.

    The model-side state is baked in (the register-file image), the
    returned function maps literals (in the path's input form) to
    ``(predictions, class_sums)``.  Prefer
    :class:`repro.serve.ServingEngine` for batched traffic — this is the
    single-step building block (the engine's own jitted step, shared
    compile cache included).
    """
    from repro.serve.engine import classify_step
    from repro.serve.paths import get_path

    name = path or servable.config.eval_path
    get_path(name)  # fail fast on unknown paths
    return functools.partial(classify_step, servable, path_name=name)
