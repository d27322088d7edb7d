"""Device idle, in ms a tick, that lies under the tick's phases before a
dispatch: ``serving.tick.admit``, ``.prefill.build``, ``.decode.build``
(``idle_under`` of ``chipbench/program_trace.py``, over the ``serving.tick``
spans that start in the traced span).  Nothing to read where the program has
no such spans."""

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
    return program_trace().idle_ms_a_tick(run, ("admit", "prefill.build", "decode.build"))
