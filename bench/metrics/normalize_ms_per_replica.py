"""Host time the program's normalize takes per replica, in milliseconds,
from its telemetry spans: the monolithic ``normalize`` span, and the
``chunk_normalize`` spans that no running chunk overlaps
(``overlapped=False``).  An overlapped span also holds the wait on device
work queued behind the running chunk, so it is left out."""


def read(ctx):
    spans = ctx.get("spans") or []
    traffic = ctx["traffic"]
    first_chunk = min(traffic.get("chunk", 0), traffic["replicas"])
    secs, reps = 0.0, 0
    for s in spans:
        if s.get("kind") != "span":
            continue
        if s["name"] == "normalize" and s.get("n_replicas"):
            secs += s["dur_s"]
            reps += s["n_replicas"]
        elif s["name"] == "chunk_normalize" and s.get("overlapped") is False \
                and s.get("chunk") == 0 and first_chunk:
            secs += s["dur_s"]
            reps += first_chunk
    return 1e3 * secs / reps if reps else None
