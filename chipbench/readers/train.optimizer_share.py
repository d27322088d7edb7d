"""Share of the device's busy time under the train step's ``optimizer`` scope
(the optax update, applying it, the health gate's selects) over ``busy_s`` of
the traced span.  Nothing to read where the trace carries no scope."""

import importlib.util
import os
import sys


def program_trace():
    """``chipbench/program_trace.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__program_trace"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "program_trace.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def read(run):
    return program_trace().scope_share(run, ("jit_step",), lambda scope, row: row[0] if scope == "optimizer" else 0.0)
