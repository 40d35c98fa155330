"""EC geometry constants (weed/storage/erasure_coding/ec_encoder.go:17-23).

DATA_SHARDS / PARITY_SHARDS / TOTAL_SHARDS define the DEFAULT geometry,
RS(10,4): what a server seals at without ``-ec.geometry`` and what a
``.vif`` that names none means. A sealed volume's geometry is the
volume's (its ``.vif``, `Geometry.of_volume_info`): code that has a volume
in hand asks the volume, never these.
"""

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS
# the reference's ShardBits is a uint32 (ec_volume_info.go): one bit a shard
MAX_TOTAL_SHARDS = 32
LARGE_BLOCK_SIZE = 1024 * 1024 * 1024  # 1 GB
SMALL_BLOCK_SIZE = 1024 * 1024  # 1 MB
EC_BUFFER_SIZE = 256 * 1024  # reference io buffer; ours batch far larger
# a local reconstruction code as gf.lrc_matrix builds it: two local groups
# (fifteen coefficients a group at most: no 32 shards reach that)
LRC_LOCAL_GROUPS = 2


class Geometry(tuple):
    """A volume's code: ``(k, m)`` is Reed-Solomon RS(k, m), ``k+m`` as an
    operator writes it (``-ec.geometry 12+4``) and as /status names it;
    ``(k, m, l)`` is a Local Reconstruction Code (Huang et al., ATC'12)
    whose ``m`` parity shards are ``l`` local ones — shard ``k + g`` the
    XOR of the ``g``-th run of ``k / l`` data shards — and ``m - l`` global
    ones, written ``k+l+g`` (``12+2+2``). A plain tuple of two or three,
    so an RS geometry compares and unpacks as ``(k, m)``."""

    __slots__ = ()

    def __new__(cls, data_shards: int, parity_shards: int,
                local_parity_shards: int = 0) -> "Geometry":
        terms = (data_shards, parity_shards)
        if local_parity_shards:
            terms += (local_parity_shards,)
        return super().__new__(cls, terms)

    def __getnewargs__(self):  # copy and pickle rebuild it from its terms
        return tuple(self)

    data_shards = property(lambda self: self[0])
    parity_shards = property(lambda self: self[1])  # local and global together
    local_parity_shards = property(lambda self: self[2] if len(self) > 2 else 0)

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    @property
    def global_parity_shards(self) -> int:
        return self.parity_shards - self.local_parity_shards

    def local_group(self, shard_id: int) -> tuple[int, ...]:
        """The shards of ``shard_id``'s local group — its run of data
        shards and their local parity, itself among them — or none for a
        global parity and for every shard of an RS code."""
        k, local = self.data_shards, self.local_parity_shards
        if not local or shard_id >= k + local:
            return ()
        size = k // local
        group = shard_id // size if shard_id < k else shard_id - k
        return (*range(group * size, (group + 1) * size), k + group)

    def __str__(self) -> str:
        if self.local_parity_shards:
            return (f"{self.data_shards}+{self.local_parity_shards}"
                    f"+{self.global_parity_shards}")
        return f"{self.data_shards}+{self.parity_shards}"

    def __repr__(self) -> str:
        return f"Geometry({', '.join(map(str, self))})"

    def checked(self) -> "Geometry":
        """Itself, if it is a code: k >= 1, m >= 1, k + m <= 32, and for an
        LRC two local groups (the paper's coefficients are chosen for two)
        of equal size and at least one global parity; ValueError
        otherwise."""
        if self.data_shards < 1 or self.parity_shards < 1:
            raise ValueError(f"ec geometry {self}: k and m must be at least 1")
        if self.total_shards > MAX_TOTAL_SHARDS:
            raise ValueError(
                f"ec geometry {self}: at most {MAX_TOTAL_SHARDS} shards a volume"
            )
        local = self.local_parity_shards
        if local:
            if local < 0 or self.data_shards % local:
                raise ValueError(
                    f"ec geometry {self}: {local} local groups do not divide "
                    f"{self.data_shards} data shards"
                )
            if local != LRC_LOCAL_GROUPS:
                raise ValueError(
                    f"ec geometry {self}: a local reconstruction code here "
                    f"has {LRC_LOCAL_GROUPS} local groups"
                )
            if self.global_parity_shards < 1:
                raise ValueError(
                    f"ec geometry {self}: at least one global parity"
                )
        return self

    @classmethod
    def parse(cls, text: str) -> "Geometry":
        """``k+m`` as in ``10+4``, or ``k+l+g`` as in ``12+2+2`` (l local
        and g global parities); anything else raises ValueError."""
        terms = str(text).split("+")
        if len(terms) not in (2, 3) or not all(
            t.isascii() and t.isdigit() for t in terms
        ):
            raise ValueError(
                f"ec geometry {text!r}: want k+m, as in 10+4, or k+l+g, "
                "as in 12+2+2"
            )
        k, *parities = map(int, terms)
        local = parities[0] if len(parities) == 2 else 0
        if len(parities) == 2 and not local:
            raise ValueError(f"ec geometry {text!r}: no local group: write k+m")
        return cls(k, sum(parities), local).checked()

    @classmethod
    def of_volume_info(cls, info: dict) -> "Geometry":
        """The geometry a ``.vif`` records; one that records none (every
        volume sealed before the key existed) is the default's, and one
        without ``local_parity_shards`` is plain RS. A .vif is input from
        outside: one that names no code raises ValueError."""
        return cls(
            int(info.get("data_shards") or DATA_SHARDS),
            int(info.get("parity_shards") or PARITY_SHARDS),
            int(info.get("local_parity_shards") or 0),
        ).checked()

    def volume_info(self) -> dict:
        """The keys of a ``.vif`` that record this code (`of_volume_info`)."""
        info = {"data_shards": self.data_shards,
                "parity_shards": self.parity_shards}
        if self.local_parity_shards:
            info["local_parity_shards"] = self.local_parity_shards
        return info


DEFAULT_GEOMETRY = Geometry(DATA_SHARDS, PARITY_SHARDS)


def shard_ext(shard_id: int) -> str:
    """Shard file extension .ec00 .. .ec13 (ec_encoder.go:64-66)."""
    return f".ec{shard_id:02d}"
