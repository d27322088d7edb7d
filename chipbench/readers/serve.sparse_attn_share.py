"""Share of the device's busy time that the serving programs spend in the
learned sparse attention: self time of the operations of ``jit_prefill*`` and
``jit_decode*`` under the scope ``attn.index`` (the indexer's projections and
its scores), ``attn.select`` (the top-k) or ``attn.sparse`` (the decoding
lanes' read of the selected K/V rows, and the attention over the selection)
over ``busy_s`` of the traced span.  The gathers of the index keys and of a
chunk's context are the pool's, under ``kv_pool.gather``, and
``serve.kv_pool_share`` reads them.
Nothing to read where the trace carries none of the three scopes (a program
without an indexer)."""

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
    value = scope_parts().share(run, PROGRAMS, lambda scopes: any(s in scopes for s in SCOPES))
    return value or None  # a program without an indexer has nothing under the scopes: no reading, not 0
