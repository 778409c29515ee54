"""Host time of the program's ``stream_flush`` scopes in the window over
the requests admitted to decode in it: what streaming each request's KV
from the prefill PE's pool row to its decode PE cost."""


def read(rec):
    t = sum(s.wall_s for s in rec.get("prof", []) if s.op == "stream_flush")
    n = rec.get("sched", {}).get("admissions", 0)
    return t / n * 1e3 if n and t else None
