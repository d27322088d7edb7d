"""Of the traced span's ticks with decoding lanes, the share whose block tables
were wider than the lanes alone need (``width > width_lanes`` of the
``serving.tick`` span): the chunk riding with them forced its width on the
decoders' gather.  Nothing to read where the spans carry no record
(``chipbench/tick_account.py``)."""

import importlib.util
import os
import sys


def tick_account():
    """``chipbench/tick_account.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__tick_account"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tick_account.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def read(run):
    account = tick_account()
    return account.read(run, account.width_forced_share)
