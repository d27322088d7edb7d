"""Of the ticks the traced span's first tokens were held, from a request's
admission to the tick whose read gave its first token, the share in which no
row of the dispatch was the request's own (1 - ``own_ticks`` / ``held_ticks``
summed over the emit spans' first tokens): it waited for its turn behind other
slots' chunks.  Nothing to read under sixteen first tokens, or where the spans
carry no such account (``chipbench/tick_account.py``)."""

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
    return account.read(run, account.turn_wait_share)
