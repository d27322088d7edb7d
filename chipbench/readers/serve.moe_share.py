"""Share of the device's busy time that the serving programs spend in the
expert layer: self time of the operations of ``jit_prefill*`` and
``jit_decode*`` that lie under the scope ``moe`` (routing, the grouped expert
product with its gathers, the shared experts) over ``busy_s`` of the traced
span.  Nothing to read where the trace carries no such scope."""

import importlib.util
import os
import sys

PROGRAMS = ("jit_prefill", "jit_decode")


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
    value = scope_parts().share(run, PROGRAMS, lambda scopes: "moe" in scopes)
    return value or None  # a program without experts has nothing under the scope: no reading, not 0
