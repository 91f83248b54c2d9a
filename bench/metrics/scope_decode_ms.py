"""Device ms per round of the ops the program names ``cpml_decode`` (the
decode of the fastest results and the gradient step), in the window, the
mean over the chips (``bench/spans.py``)."""

from bench import spans


def read(m):
    return spans.scope_ms(m, "cpml_decode")
