"""Share of the busiest chip's op time, in %, under none of the program's
scopes (model, ZeRO-3, optimizer, schedule executor): ops the compiler
added without a source, or whose program could not be joined
(bench/scopes.py). None where no op carries a scope."""
import scopes


def read(run: dict):
    r = scopes.of_run(run)
    if not r.get("by_layer") or not r["op_s"]:
        return None
    return 100.0 * r["unscoped_s"] / r["op_s"]
