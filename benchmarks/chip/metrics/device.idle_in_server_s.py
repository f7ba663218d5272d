"""Device idle seconds per round inside the server's host work: the first
chip's idle time within the union of the ``server.ingest`` and
``server.aggregate`` spans of the traced window, over its rounds.

Also prints to stderr the idle seconds of the window by the innermost
program span they fell in, and the share of the idle time inside the
harness's round spans that falls in no program span. Returns nothing
where the program opens no such spans."""
import json
import sys

from fdbench import spans

SERVER = ("server.ingest", "server.aggregate")


def read(ctx):
    got = spans.of(ctx)
    if got is None:
        return None
    table, inside = got.idle_by_span()
    rows = dict(sorted(table.items(), key=lambda kv: -kv[1]))
    share = 100.0 * table[spans.NO_SPAN] / inside if inside > 0 else 0.0
    print(f"idle seconds by innermost program span in the traced window "
          f"({got.rounds} rounds): {json.dumps(rows)}; {share!r}% of the "
          f"{inside!r} idle seconds inside the round spans falls in no "
          f"program span", file=sys.stderr)
    return got.idle_in_s(SERVER)
