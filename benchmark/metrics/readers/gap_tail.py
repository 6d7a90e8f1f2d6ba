"""The 95th percentile of the gaps between token deliveries that are not
zero (a decode window's tokens arrive together): the step time a streaming
client waits at worst, whatever share of the steps is a bare window."""
import numpy as np


def read(ctx):
    gaps = [g for g in ctx.get('gaps', ()) if g > 0.0]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
