"""Seconds of set-up the trainer spent in first calls of step variants:
the sum of its `trainer/step_compile` spans."""


def read(run):
    spans = [e.dur_us for e in run.spans if e.name == "trainer/step_compile"]
    return sum(spans) * 1e-6 if spans else None
