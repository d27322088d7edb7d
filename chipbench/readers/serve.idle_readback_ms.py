"""Device idle, in ms a tick, that lies under the tick's phases from a dispatch
on: ``serving.tick.prefill.wait``, ``.prefill.emit``, ``.decode.wait``,
``.decode.emit``, ``.publish``.  Under a ``wait`` the idle is what passes before
the program's first operation starts (the launch) and after its last one ends
(the sync); the rest of a ``wait`` the device is busy.  Nothing to read where
the program has no such spans."""

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
    return program_trace().idle_ms_a_tick(
        run, ("prefill.wait", "prefill.emit", "decode.wait", "decode.emit", "publish")
    )
