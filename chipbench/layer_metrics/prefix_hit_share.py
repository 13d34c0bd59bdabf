"""prefix_hit_share (%, cache manager): prompt tokens the prefix cache
served (``Request.prefix_hit_tokens``) over the prompt tokens of the
requests due in the window that were admitted.  Moves ttft_p90_s."""


def read(run):
    rs = [r for r in run.recs
          if run.w0 <= r.due < run.w1 and r.req.t_admit is not None]
    total = sum(len(r.prompt) for r in rs)
    return 100.0 * sum(r.req.prefix_hit_tokens for r in rs) / total if total else None
