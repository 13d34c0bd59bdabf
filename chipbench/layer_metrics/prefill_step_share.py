"""prefill_step_share (%, engine / scheduler): the ``engine.step`` spans
(``StepStats.step_s``) of the window's steps that prefilled prompt tokens
over those of all the window's steps.  Silent where the engine keeps no
step spans.  Moves output_tok_s."""


def read(run):
    steps = [st for h in run.histories for st in h
             if run.w0 <= st.t < run.w1 and getattr(st, "step_s", 0.0) > 0]
    total = sum(st.step_s for st in steps)
    if total <= 0:
        return None
    return 100.0 * sum(st.step_s for st in steps if st.prefill_tokens) / total
