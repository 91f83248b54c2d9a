"""Device ms per round of the ops the program names ``cpml_encode_weights``
(the master's encode of each round's weights), in the window, the mean over
the chips (``bench/spans.py``)."""

from bench import spans


def read(m):
    return spans.scope_ms(m, "cpml_encode_weights")
