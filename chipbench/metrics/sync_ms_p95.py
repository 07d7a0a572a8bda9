"""95th percentile, over every tenant iteration due in the window, of the
milliseconds from when its push was due to when its pulled parameters were
ready on the device."""

import numpy as np


def read(run):
    d = [ready - due for _, due, _, ready in run.iters if due < run.t_end]
    return 1e3 * float(np.percentile(d, 95)) if d else None
