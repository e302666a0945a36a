"""Share of device time whose owner the program's labels name: seconds under
a leaf of `MODEL_SCOPES` other than the catch-all `loss`, over all exclusive
device seconds, in percent. `loss` wraps the whole differentiated model, so an
operation that has lost its model scope still has a leaf, `loss`: counted as
covered it would hide the loss (the program's own `coverage` does count it).
The objective's own arithmetic is then uncovered too: 0.002 ms a step in
`d4_onehead_train`."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
try:
    import _program_profile as prog  # noqa: E402
except ImportError:   # a checkout that lacks the helper reads nothing
    prog = None

CATCH_ALL = 'loss'


def _read(ctx):
    red = prog.profile(ctx, __file__)
    if red is None or not red['device_s']:
        return None
    owned = red['labelled_s'] - red['leaf_s'].get(CATCH_ALL, 0.0)
    return 100.0 * owned / red['device_s']


def read(ctx):
    return prog and prog.or_nothing(_read, ctx)
