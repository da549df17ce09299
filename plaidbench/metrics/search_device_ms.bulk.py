"""Pipeline: device time of one search batch, the mean duration of the
search program's runs (``XLA Modules`` events of ``jit_run_pipeline_impl``
on the first chip), in ms."""


def read(ctx):
    ev = ctx["trace"].module_events(lambda n: "run_pipeline" in n)
    if not ev:
        return None
    return sum(e.end - e.start for e in ev) / len(ev) / 1e6
