"""Anti-entropy entry: median milliseconds, call by call, of ``mesh_fold``
(the ``ae.fold`` program span) outside its ``ae.fold.wait`` span: the
entry's host work around one fold."""

from program_spans import median_less_ms


def read(c):
    return median_less_ms("ae.fold", "ae.fold.wait")
