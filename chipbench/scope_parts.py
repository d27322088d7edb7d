"""Device time by the named scopes an operation lies under, anywhere in its
``op_name``: for scopes ``program_trace.SCOPES`` does not list.

``program_trace.innermost_scope`` files an operation under the last part of its
``op_name`` that is one of ``SCOPES``; the expert layer's scopes (``moe``,
``moe.route``, ``moe.experts``, ``moe.shared``, inside ``mlp``) and the latent
attention's (``attn.latent``, ``attn.absorb``, inside ``attn``) are not among
them, so the accepted readers see the enclosing ``mlp`` or ``attn``, and the
readers of PR 28 ask here for the parts themselves.  One more rule: XLA:TPU
turns ``lax.ragged_dot`` into a Mosaic grouped-matmul kernel whose ``op_name`` is
``ragged-dot-*`` and nothing else, the scope path lost; such an operation counts
under ``moe`` and ``moe.experts``, where the program's only ``ragged_dot`` lies.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
GROUPED_PRODUCT = "ragged-dot"  # the kernel's name in op_name and in the event's HLO line
GROUPED_PRODUCT_SCOPES = ("moe", "moe.experts")


def program_trace():
    """``chipbench/program_trace.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__program_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "program_trace.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def scopes_of(event_name: str, op_name: str) -> tuple:
    """Every named part of ``op_name`` (autodiff's ``jvp(x)`` reads ``x``), outermost first."""
    if op_name.startswith(GROUPED_PRODUCT) or event_name.lstrip("%").startswith(GROUPED_PRODUCT):
        return GROUPED_PRODUCT_SCOPES
    parts = []
    for part in op_name.split("/"):
        words = re.findall(r"[A-Za-z_][\w.]*", part)
        if words:
            parts.append(words[-1])
    return tuple(parts)


def self_seconds(run: dict, programs, wanted) -> float | None:
    """Self time, over the traced span, of the operations of the programs named
    ``programs*`` for which ``wanted(scopes)`` holds; ``None`` where there is no
    trace, or no operation of those programs in it."""
    path = run["traced"].get("raw_path")
    if not path:
        return None
    pt = program_trace()
    window = pt.traced_window(path)
    if window is None:
        return None
    t0, t1 = window
    total, found = 0.0, False
    for name, prog, start, dur, _, self_s, op_name in pt.load(path)["ops"]:
        if not prog.startswith(tuple(programs)) or start >= t1 or start + dur <= t0:
            continue
        found = True
        if wanted(scopes_of(name, op_name)):
            total += self_s
    return total if found else None


def share(run: dict, programs, wanted) -> float | None:
    """``self_seconds`` as a percentage of the traced span's ``busy_s``."""
    busy = (run["traced"].get("trace") or {}).get("busy_s")
    seconds = self_seconds(run, programs, wanted)
    return None if not busy or seconds is None else 100.0 * seconds / busy
