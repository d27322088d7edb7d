"""Share of the device's busy time that the serving programs spend in the
sliding layers' attention and on their ring of blocks: self time of the
operations of ``jit_prefill*`` and ``jit_decode*`` under the scope
``attn.window`` (a sliding layer's scores, softmax and PV over the rows its
window table names) or ``kv_pool.window`` (the gather through the window
tables, the overlay of the new rows, their scatter into the ring) over
``busy_s`` of the traced span.  Nothing to read where the trace carries
neither scope (a program without window leaves)."""

import importlib.util
import os
import sys

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPES = ("attn.window", "kv_pool.window")


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
    return value or None  # a program without window leaves has nothing under the scopes: no reading, not 0
