"""Seconds of set-up spent getting `train_step`'s executable: the backend
compile, or the cache lookup and load on a hit (the program's compile log)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
try:
    import _program_profile as prog  # noqa: E402
except ImportError:   # a checkout that lacks the helper reads nothing
    prog = None


def _read(ctx):
    secs = prog.compile_seconds(ctx)
    return None if secs is None else secs['load_s']


def read(ctx):
    return prog and prog.or_nothing(_read, ctx)
