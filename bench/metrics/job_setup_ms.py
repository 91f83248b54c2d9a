"""Host ms of a training job's own set-up, as it runs in the window: from
the start of the program's ``cpml.train`` phase to the first device op of
that job's scan, the mean over jobs and chips. Further keys: the mean ms a
job of each ``cpml.setup.*`` phase (``bench/spans.py``)."""

from bench import spans


def read(m):
    return spans.job_setup(m.window, m.lo_ns, m.hi_ns)
