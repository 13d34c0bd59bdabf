"""queue_wait_p90_s (s, engine / scheduler): the 90th percentile, over the
requests due in the window that were admitted, of admission (the engine's
``Request.t_admit``) minus the due time.  Moves ttft_p90_s."""
import stats


def read(run):
    v = [r.req.t_admit - r.due for r in run.recs
         if run.w0 <= r.due < run.w1 and r.req.t_admit is not None]
    return stats.percentile(v, 90) if v else None
