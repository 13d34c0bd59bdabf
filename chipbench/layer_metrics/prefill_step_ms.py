"""prefill_step_ms (ms, model step): the median ``StepStats.step_s`` (the
``engine.step`` span) of the window's steps that prefilled prompt tokens:
bucket prefills or the chunk program, with the decode that follows them in
the step.  Silent where the engine keeps no step spans or the window held
no prefill.  Moves itl_p95_s."""
import statistics


def read(run):
    v = [st.step_s for h in run.histories for st in h
         if run.w0 <= st.t < run.w1 and st.prefill_tokens
         and getattr(st, "step_s", 0.0) > 0]
    return 1e3 * statistics.median(v) if v else None
