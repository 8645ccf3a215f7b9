"""An untraced run of a cell never turns on the program's own tracer
(``repro.obs.trace``): the engine's spans stay the shared no-op, so the
measured window pays nothing for them."""
from bench.tests import tiny


def test_untraced_run_never_enables_the_program_tracer():
    from repro.obs import trace as obs_trace
    seen = []

    def watch(eng):
        step = eng.step

        def watched():
            seen.append(obs_trace.enabled())
            return step()
        eng.step = watched

    res, _ = tiny.run(tiny.SERVE, tiny.serve_cell(), hooks={"engine": watch})
    assert res["correct"]
    assert seen and not any(seen)
    assert not obs_trace.enabled()
