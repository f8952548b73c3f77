"""Planted faults: the timed path broken underneath, for showing that the
comparison refuses it (``tests/test_faults.py`` on the CPU at a small
fleet, ``control.py`` on the chip at the cell's own size).

* ``unchanged`` - the decide returns the state it was given (no lane's
  allocation moves, every action ``none``);
* ``half`` - only the first half of the fleet is decided; the second
  half gets the first half's answers;
* ``altered`` - one lane's answer is changed where it is produced: a
  processor moves from its first operator to its second, applied.

The exchange between chips is not a fault these cells can have: every
cell runs on one chip and no collective lies on its path.
"""

from __future__ import annotations

import numpy as np

FAULTS = ("unchanged", "half", "altered")


def plant(fault, code, k_next, et_cur, et_target, applied, k_cur):
    """The fault applied to one tick's outputs (numpy, lanes leading)."""
    code, k_next, applied = np.array(code), np.array(k_next), np.array(applied)
    et_cur, et_target = np.array(et_cur), np.array(et_target)
    if fault == "unchanged":
        return np.zeros_like(code), np.array(k_cur), et_cur, et_target, np.zeros_like(applied)
    if fault == "half":
        h = code.shape[0] // 2
        outs = [code, k_next, et_cur, et_target, applied]
        for x in outs:
            x[h:2 * h] = x[:h]
        return tuple(outs)
    k_next[0, 0] -= 1
    k_next[0, 1] += 1
    applied[0] = True
    return code, k_next, et_cur, et_target, applied


def wrap_with(fault):
    """A function that plants ``fault`` in what a built cell's window calls
    (the service's decide or the twin's loop)."""

    def wrap(cell):
        if hasattr(cell, "loop"):
            real_loop = cell.loop

            def loop(k0):
                out = dict(real_loop(k0))
                ticks = out["codes"].shape[0]
                planted = [
                    plant(fault, out["codes"][t], out["k"][t], out["et_cur"][t],
                          out["et_target"][t], out["applied"][t],
                          k0 if t == 0 else out["k"][t - 1])
                    for t in range(ticks)
                ]
                for i, key in enumerate(("codes", "k", "et_cur", "et_target", "applied")):
                    out[key] = np.stack([p[i] for p in planted])
                return out

            cell.loop = loop
            return
        real = cell.decide

        def decide(*args):
            if cell.compact:
                out, repriced, cache = real(*args)
                return plant(fault, *out, args[4]), repriced, cache
            return plant(fault, *real(*args), args[4])

        decide.init_cache = getattr(real, "init_cache", None)
        cell.decide = decide

    return wrap
