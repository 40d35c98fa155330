"""EC geometry constants (weed/storage/erasure_coding/ec_encoder.go:17-23).

DATA_SHARDS / PARITY_SHARDS / TOTAL_SHARDS define the DEFAULT geometry,
RS(10,4): what a server seals at without ``-ec.geometry`` and what a
``.vif`` that names none means. A sealed volume's geometry is the
volume's (its ``.vif``, `Geometry.of_volume_info`): code that has a volume
in hand asks the volume, never these.
"""

from typing import NamedTuple

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS
# the reference's ShardBits is a uint32 (ec_volume_info.go): one bit a shard
MAX_TOTAL_SHARDS = 32
LARGE_BLOCK_SIZE = 1024 * 1024 * 1024  # 1 GB
SMALL_BLOCK_SIZE = 1024 * 1024  # 1 MB
EC_BUFFER_SIZE = 256 * 1024  # reference io buffer; ours batch far larger


class Geometry(NamedTuple):
    """A Reed-Solomon code's shape, RS(k, m): ``k+m`` as an operator writes
    it (``-ec.geometry 12+4``) and as /status names it."""

    data_shards: int
    parity_shards: int

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    def __str__(self) -> str:
        return f"{self.data_shards}+{self.parity_shards}"

    def checked(self) -> "Geometry":
        """Itself, if it is a code: k >= 1, m >= 1, k + m <= 32; ValueError
        otherwise."""
        if self.data_shards < 1 or self.parity_shards < 1:
            raise ValueError(f"ec geometry {self}: k and m must be at least 1")
        if self.total_shards > MAX_TOTAL_SHARDS:
            raise ValueError(
                f"ec geometry {self}: at most {MAX_TOTAL_SHARDS} shards a volume"
            )
        return self

    @classmethod
    def parse(cls, text: str) -> "Geometry":
        """``k+m``, as in ``10+4``; anything else raises ValueError."""
        k, plus, m = str(text).partition("+")
        if not (plus and k.isascii() and k.isdigit() and m.isascii() and m.isdigit()):
            raise ValueError(f"ec geometry {text!r}: want k+m, as in 10+4")
        return cls(int(k), int(m)).checked()

    @classmethod
    def of_volume_info(cls, info: dict) -> "Geometry":
        """The geometry a ``.vif`` records; one that records none (every
        volume sealed before the key existed) is the default's. A .vif is
        input from outside: one that names no code raises ValueError."""
        return cls(
            int(info.get("data_shards") or DATA_SHARDS),
            int(info.get("parity_shards") or PARITY_SHARDS),
        ).checked()


DEFAULT_GEOMETRY = Geometry(DATA_SHARDS, PARITY_SHARDS)


def shard_ext(shard_id: int) -> str:
    """Shard file extension .ec00 .. .ec13 (ec_encoder.go:64-66)."""
    return f".ec{shard_id:02d}"
