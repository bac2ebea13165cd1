"""Mean host milliseconds per step inside the trainer's batch function
(``run.next_wbatch``), from the benchmark's span around that call."""


def read(ctx):
    spans = (ctx.get("spans") or {}).get("next_wbatch")
    if not spans:
        return None
    return 1000.0 * sum(spans) / len(spans)
