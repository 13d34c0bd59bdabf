"""decode_step_ms (ms, model step): the median of the engines' own
``StepStats.decode_s`` (host clock around the decode program and the
sampled tokens' copy to the host) over the window's steps that prefilled
nothing after a step that prefilled nothing (a chunk program is not waited
for in its own step, so its time lands in the next step's decode).  Moves
itl_p95_s."""
import statistics


def read(run):
    v = [st.decode_s for h in run.histories for prev, st in zip(h, h[1:])
         if run.w0 <= st.t < run.w1 and st.tokens_out
         and not st.prefill_tokens and not prev.prefill_tokens]
    return 1e3 * statistics.median(v) if v else None
