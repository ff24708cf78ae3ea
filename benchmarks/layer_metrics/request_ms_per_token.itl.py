"""`request_ms_per_token` as `reduce_requests` reckons it (from the due
time to the last token over the tokens asked for, mean over ALL requests),
recorded and not judged in `gpt2-chat`: the driver's check of PR 23 read
it there with a spread of 6.4% in one set of six and 1.1% in the other,
more than any bound may allow (PERF.md section 2). A mean feels every
stall of the host; `itl_p95_ms`, which is judged, does not."""


def read(obs):
    return (obs.get("numbers") or {}).get("request_ms_per_token")
