"""Of the executions of the programs whose name matches `of`, the share
whose name matches `pattern`, from the trace's per-program events."""
import re


def read(ctx, pattern, of):
    of, pattern = re.compile(of), re.compile(pattern)
    names = [name for rows in ctx['trace'].programs.values()
             for name, _, _ in rows if of.search(name)]
    if not names:
        return None
    return 100.0 * sum(bool(pattern.search(n)) for n in names) / len(names)
