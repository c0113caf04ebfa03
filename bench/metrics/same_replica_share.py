"""Router: share of the window's follow-up requests that the router placed
on the replica that had served the same user's previous request, where
that request's pages are cached.  A user's requests extend one history,
so they share their first page of tokens (``serve.kv_page_tokens``); the
previous request may be the session prime sent in set-up.  Nothing to
read without follow-up requests."""


def read(run):
    page = int(run.cfg["serve"]["kv_page_tokens"])
    sent = [(r, False) for r in run.primes] + [(r, True)
                                               for r in run.requests]
    last, same, follow = {}, 0, 0
    for r, in_window in sorted(sent, key=lambda x: x[0].submit_t):
        user = r.prompt[:page].tobytes()
        if in_window and user in last:
            follow += 1
            same += last[user] == r.replica
        last[user] = r.replica
    return 100.0 * same / follow if follow else None
