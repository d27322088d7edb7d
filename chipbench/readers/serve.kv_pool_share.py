"""Share of the device's busy time that the serving programs spend on the paged
pool: self time of the operations of ``jit_prefill*`` and ``jit_decode*`` whose
``op_name`` lies under a ``kv_pool`` scope (``kv_pool.gather``: the gather
through the block tables and what it pulls in; ``kv_pool.write``: the scatter
of the new rows) over ``busy_s`` of the traced span.  Nothing to read where
the trace carries no scope."""

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
    return program_trace().scope_share(
        run, ("jit_prefill", "jit_decode"), lambda scope, row: row[0] if scope.startswith("kv_pool") else 0.0
    )
