"""Device ms per round of the ops the program names ``cpml_worker`` (the
worker polynomial of every round the window ran, in ``_round`` or in the
training scan), the mean over the chips; the op's scope is the ``op_name``
of its HLO instruction (``bench/spans.py``)."""

from bench import spans


def read(m):
    return spans.scope_ms(m, "cpml_worker")
