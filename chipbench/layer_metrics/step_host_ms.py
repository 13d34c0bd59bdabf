"""step_host_ms (ms, engine / scheduler): the mean, over the window's
steps, of the engines' ``StepStats.step_s - StepStats.wait_s``: the
``engine.step`` span less the ``engine.wait`` spans inside it, the host's
own time in a step while the device works or waits for it.  Silent where
the engine keeps no step spans.  Moves itl_p95_s."""
import statistics


def read(run):
    v = [st.step_s - st.wait_s for h in run.histories for st in h
         if run.w0 <= st.t < run.w1 and getattr(st, "step_s", 0.0) > 0]
    return 1e3 * statistics.fmean(v) if v else None
