"""The learned sparse attention's share of its roofline over the traced span.

Least time: for every lane of every group of a dispatch, a layer reads the
index keys of its whole context ``L`` and the K/V rows of one selection,
``min(L, topk)`` (``families/<family>.py:sparse_least_bytes`` of the engine's
``index_rows_scored`` and ``sparse_lane_rows`` over the traced span), at the
chip's peak bytes/s.  Over the self time of the operations of ``jit_prefill*``
and ``jit_decode*`` under the scopes ``attn.index``, ``attn.select`` and
``attn.sparse``.  The same count whatever implements the path, and for a
prefill chunk, whose queries each choose their own rows, the bound from below:
it cannot pass 100 (the gathers of the index keys and of a chunk's context lie
under ``kv_pool.gather``, but the scores under ``attn.index`` read every
gathered index key again, and the attention every K/V row it weighs).  Nothing to read without the counters or the scopes."""

import importlib.util
import os
import sys

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPES = ("attn.index", "attn.select", "attn.sparse")


def scope_parts():
    """``chipbench/scope_parts.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__scope_parts"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scope_parts.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def read(run):
    least = getattr(run["family"], "sparse_least_bytes", None)
    least_bytes = least(run["cfg"], run["traced"].get("counters") or {}) if least else None
    if not least_bytes:
        return None
    seconds = scope_parts().self_seconds(run, PROGRAMS, lambda scopes: any(s in scopes for s in SCOPES))
    if not seconds:
        return None
    return 100.0 * least_bytes / run["peak_bytes"] / seconds
