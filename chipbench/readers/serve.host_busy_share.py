"""The share of the ticks' time in which the host's thread was busy, over the
traced span: the sum over ticks of the start-to-start period less the
``serving.tick.read`` spans in it (the one place the host is blocked on the
device), over the sum of the periods.  What the driver does between two ticks
(``submit``, ``pop_finished``) counts: it runs on the same thread.  At 100 the
device waits for the host.  Nothing to read where the program's
``serving.tick`` spans carry no record (``chipbench/tick_account.py``)."""

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
    return account.read(run, account.host_busy_share)
