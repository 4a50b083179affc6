"""Host time of the serving engine's per-slot uniform draws per sweep, in
ms: the summed duration of the program's ``repro.serve.uniforms`` spans
over the number of ``repro.serve.step`` spans in the traced window."""

from benchlib import spans


def read(r):
    s = spans.read(r)
    return None if s is None else s.per_ms("repro.serve.uniforms",
                                           "repro.serve.step")
