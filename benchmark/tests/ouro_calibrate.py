"""Run on the chip, not a test: `hybrid_calibrate.py` for the looped
decoder's cell, whose comparison has no choices and has the exits instead
(`harness/ouro_train.py::compare`): the same loop over seeds with this
file's `gaps` in the place of `lm_calibrate.gaps`.

    python3 benchmark/tests/ouro_calibrate.py <cell> <fp8> <seed> [...]

One JSON line per seed goes to chiprun_out/lm_calibrate_<cell>_<first
seed>.jsonl, with every leaf's numbers, the first step's exits entry by
entry (`loss_ut`, `exit_share`) and what the program read there (`exits`).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import hybrid_calibrate  # noqa: E402  (puts the bench on the path)


def gaps(prog, ref):
    """The numbers `correct` compares, with every leaf's (calibrate.py's),
    and the first step's exits entry by entry."""
    from calibrate import _gaps
    from harness.ouro_train import EXITS
    return dict(_gaps(prog, ref), exits={k: prog[k] for k in EXITS}, **{
        key: [abs(a - b) / abs(b) for a, b in zip(prog[key], ref[key])]
        for key in EXITS})


if __name__ == '__main__':
    hybrid_calibrate.gaps = gaps
    hybrid_calibrate.main()
