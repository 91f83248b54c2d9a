"""Device ms of one round's worker polynomial, all N workers: the runs of
the probe program that jits ``protocol.all_worker_results`` on the cell's
shapes, from the trace."""


def read(m):
    return m.probe_ms("bench_worker_step")
