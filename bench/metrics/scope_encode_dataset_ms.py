"""Device ms a training job of the ops the program names
``cpml_encode_dataset`` (each job's dataset encode; across chips, every
chip's row blocks of its own shares), in the window, the mean over the
chips. A job is one of the program's ``cpml.train`` phases in the window
(``bench/spans.py``)."""

from bench import spans


def read(m):
    jobs = spans.within(m.window.host, spans.TRAIN, m.lo_ns, m.hi_ns)
    per_round = spans.scope_ms(m, "cpml_encode_dataset")
    if not jobs or per_round is None:
        return None
    return per_round * m.rounds / len(jobs)
