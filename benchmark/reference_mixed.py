"""The plain reference of the configuration ``mixed1``: a node that holds
volumes of two codes — Reed-Solomon RS(k, m) as ``reference.py`` has it
(klauspost's, SeaweedFS's only code) and Azure's LRC(12,2,2) as
``reference_lrc.py`` has it — and reads each at the code it was sealed
with. A code is the ``ec`` object of a configuration: with
``local_parity_shards`` it is the local reconstruction code, without it
Reed-Solomon.

Beside the shard sums of each code it gives what ONE degraded read
recovers from: the shards that determine a single wanted shard under a
loss. That is not ``reference_lrc.read_set`` of the loss, which rebuilds
every lost shard at once (Huang et al., ATC'12, sec. 2.1): a read wants one
shard, and where that shard is the only loss among its local group's seven
it is the sum of the six others, whatever else the volume has lost.

It imports the benchmark's two references and nothing of the program, and
never touches JAX.
"""

from __future__ import annotations

from . import reference, reference_lrc


def is_lrc(ec: dict) -> bool:
    return bool(ec.get("local_parity_shards"))


def name(ec: dict) -> str:
    """The code as the program's ``-ec.geometry`` writes it: ``10+4``,
    ``12+2+2`` (data + local + global parities)."""
    k, m, local = ec["data_shards"], ec["parity_shards"], ec.get(
        "local_parity_shards", 0)
    return f"{k}+{local}+{m - local}" if local else f"{k}+{m}"


def total_shards(ec: dict) -> int:
    return ec["data_shards"] + ec["parity_shards"]


def shard_sums(dat_path: str, ec: dict, threads: int = 8) -> dict:
    """SHA-256 of every shard file a correct seal of ``dat_path`` at ``ec``
    writes, by the reference of that code."""
    ref = reference_lrc if is_lrc(ec) else reference
    return ref.shard_sums(dat_path, ec, threads=threads)


def decodable(ec: dict, lost) -> bool:
    """RS(k, m) bears any m losses; LRC(12,2,2) what the paper's counting
    rule admits."""
    if is_lrc(ec):
        return reference_lrc.decodable(lost)
    return len(set(lost)) <= ec["parity_shards"]


def sole_loss_group(ec: dict, wanted: int, lost) -> list[int] | None:
    """The seven fragments of the wanted shard's local group where it is
    the only one of them lost; None otherwise and for Reed-Solomon."""
    if is_lrc(ec):
        for group in range(reference_lrc.GROUPS):
            seven = reference_lrc.members(group)
            if wanted in seven and (set(lost) | {wanted}) & set(seven) == {wanted}:
                return seven
    return None


def read_set(ec: dict, wanted: int, lost) -> list[int]:
    """The shards one recovery of ``wanted`` reads when ``lost`` are gone.

    RS(k, m): any k survivors determine every shard; the k lowest stand for
    them (only their number is compared). LRC(12,2,2): the six others of
    the wanted shard's local group where it is the only loss among the
    group's seven; else the wanted shard is solved with the rest of the
    loss over all twelve data fragments, ``reference_lrc.read_set(lost)``.
    ValueError for a loss the code does not decode."""
    lost = set(lost) | {wanted}
    if not decodable(ec, lost):
        raise ValueError(f"{name(ec)} does not decode the loss of {sorted(lost)}")
    seven = sole_loss_group(ec, wanted, lost)
    if seven:
        return [s for s in seven if s != wanted]
    if is_lrc(ec):
        return reference_lrc.read_set(lost)
    survivors = [s for s in range(total_shards(ec)) if s not in lost]
    return survivors[:ec["data_shards"]]


def is_local(ec: dict, wanted: int, lost) -> bool:
    """Whether the wanted shard's own local group sufficed."""
    return sole_loss_group(ec, wanted, lost) is not None
