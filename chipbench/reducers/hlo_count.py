"""How many instructions of the compiled step match any of ``patterns``
(regular expressions, searched in each instruction's line of the HLO text).
A count from the program, so it repeats exactly."""

import re


def reduce(measured, params):
    patterns = [re.compile(p) for p in params["patterns"]]
    return sum(1 for line in measured.hlo.splitlines()
               if " = " in line and any(p.search(line) for p in patterns))
