"""Rows of the traced span's dispatches that belonged to a request (the live
lanes' windows and the chunk's real rows: ``rows_live`` of the ``serving.tick``
spans) over the rows the programs computed (``max_slots`` windows and the whole
padded chunk: ``rows_computed``): idle lanes and the chunk's padding are
computed, and routed, like real rows.  Nothing to read where the spans carry no
record (``chipbench/tick_account.py``)."""

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
    return account.read(run, account.row_fill)
