"""Share of the device's busy time that the serving programs spend in the
short-convolution operator and its state: self time of the operations of
``jit_prefill*`` and ``jit_decode*`` under the scope ``conv`` (``conv.in``: the
projection ``W_in``; ``conv.mix``: the gates and the three taps; ``conv.out``:
``W_out``) or ``state_pool`` (the state's read and its write by slot) over
``busy_s`` of the traced span: what the operator costs with its state.  Nothing
to read where the trace carries no such scope."""

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
    value = scope_parts().share(run, PROGRAMS, lambda scopes: "conv" in scopes or "state_pool" in scopes)
    return value or None  # a program without the operator has nothing under the scopes: no reading, not 0
