"""batch_occupancy (rows, engine / scheduler): the mean of the engines'
``StepStats.occupancy`` over the window's decode steps.  Moves
output_tok_s."""
import statistics


def read(run):
    v = [st.occupancy for h in run.histories for st in h
         if run.w0 <= st.t < run.w1 and st.tokens_out]
    return statistics.fmean(v) if v else None
