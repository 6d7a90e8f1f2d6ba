"""The numbers that decide `correct`, each beside its limit."""
from __future__ import annotations

import math
import sys


class Verdict:
    def __init__(self):
        self.rows = {}          # name -> (value, limit)

    def hold(self, name, value, limit):
        """`value` must be a number at or under `limit`; a NaN fails."""
        self.rows[name] = (float(value), float(limit))

    @property
    def correct(self):
        return bool(self.rows) and all(
            not math.isnan(v) and v <= lim for v, lim in self.rows.values())

    def compared(self):
        return {n: {'value': v, 'limit': lim}
                for n, (v, lim) in self.rows.items()}

    def report(self):
        for n, (v, lim) in self.rows.items():
            print(f'compared {n}: {v:.6g} (limit {lim:.6g}) '
                  f'{"ok" if v <= lim else "NOT OK"}', file=sys.stderr)
        print(f'correct: {self.correct}', file=sys.stderr, flush=True)


def judged(readings, limits):
    """A control or a planted fault in the program's place: for each name
    in `readings` ({name: {number: value}}) its numbers held to the cell's
    own `limits`, and whether it came out correct. It must not."""
    out = {}
    for name, numbers in readings.items():
        held = Verdict()
        for number, limit in limits.items():
            held.hold(number, numbers[number], limit)
        out[name] = {'correct': held.correct, 'compared': held.compared()}
        bad = [n for n, (v, lim) in held.rows.items() if not v <= lim]
        print(f'control {name}: correct {held.correct} (fails '
              f'{", ".join(bad) or "nothing"})', file=sys.stderr)
    return out
