"""Device ms of one round's decode: the runs of the probe program that jits
``protocol.decode_gradient`` on ``threshold`` results, from the trace."""


def read(m):
    return m.probe_ms("bench_decode_step")
