"""Share of the device's busy time that the serving programs spend in the layer
loop itself: self time of the operations of ``jit_prefill*`` and
``jit_decode*`` whose innermost scope is ``layers``, so what the scan does
around its body (slicing its scanned inputs, stacking its outputs) and nothing
a finer scope names (``attn.*``, ``mlp``, ``kv_pool.*``), over ``busy_s`` of
the traced span.  Until PR 27 the paged pool was a scanned input and every
layer's whole slice of it was cut out and re-tiled here (47% of busy time);
the share is the witness that those copies have not come back.  Nothing to
read where the trace carries no scope."""

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
        run, ("jit_prefill", "jit_decode"), lambda scope, row: row[0] if scope == "layers" else 0.0
    )
