"""session_affinity_share (%, router): of the turns due in the window that
were admitted and follow an earlier turn of their session, the share the
router (``Request.replica``, set by ``Orchestrator.submit``) sent to the
replica it sent that earlier turn to, where the session's history blocks
were cached.  The previous turn is the session's record in the run, or,
for the first turn a session under way sends in the run, the longest
primed prompt (its last turn before the run with its answer) that opens
this turn's prompt.  Moves ttft_p90_s."""


def read(run):
    by = {(r.session, r.turn): r for r in run.recs}

    def before(r):
        prev = by.get((r.session, r.turn - 1))
        if prev is not None:
            return prev.req.replica
        opens = [(len(p), rep) for p, rep in run.primed
                 if len(p) <= len(r.prompt) and r.prompt[:len(p)] == p]
        return max(opens)[1] if opens else None
    same = [rep == r.req.replica for r in run.recs
            if run.w0 <= r.due < run.w1 and r.req.t_admit is not None
            and r.turn > 0 and (rep := before(r)) is not None]
    return 100.0 * sum(same) / len(same) if same else None
