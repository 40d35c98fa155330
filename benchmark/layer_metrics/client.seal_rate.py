"""``seal_rate`` — the ``.dat``'s bytes times the window's seals over the
seconds those seals took TOGETHER, every stall in it — in the maintain cells
where it is no end-to-end metric (``warm1.maintain``, ``geom124.maintain``
since PR 47): paced, the same seeds run twice read 14.1% and 25.6% wide there
(27.2% and 51.5%), the farthest run left out, more than half of the widest
bound the contract allows, because a seal's commit has two levels on the
check machine's ``9p`` mount, a third of a seal apart, and a set of runs
spans both (PERF.md sections 2 and 6). ``seal_rate`` stays end to end where
the sets held it: ``warm1.maintain-1lost`` seals exactly as
``warm1.maintain`` does, ``lrc1222.maintain-1lost-local`` sixteen shards from
twelve rows as ``geom124.maintain`` does, ``mesh4.maintain`` across chips.
Here the same number is on every traced line, and on every run's
``[readings]`` line and in its ``readings``: the ledger's history of
``seal_rate`` in these two cells continues under this name."""
LAYER = "client"
UNIT = "MB/s"
MOVES = "rebuild_rate"
SOURCE = "host_clock"


def read(ctx):
    from benchmark.generators.maintain_cycle import rates

    client = ctx["client"]
    return rates(client.get("dat_bytes"), client.get("seal_s") or [],
                 client.get("rebuild_s") or [])["seal_rate"]
