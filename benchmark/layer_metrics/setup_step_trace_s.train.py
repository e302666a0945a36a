"""Seconds of set-up spent tracing `train_step` to a jaxpr and lowering it to
MLIR (the program's compile log)."""
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
    return None if secs is None else secs['trace_s']


def read(ctx):
    return prog and prog.or_nothing(_read, ctx)
