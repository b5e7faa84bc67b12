"""Feature gates (ref: src/flamenco/features/ — fd_features.h registry of
~200 feature pubkeys with activation slots).

A feature is active from its activation slot onward; the registry maps
feature name -> activation slot (None = not scheduled).  Runtime code
branches on `features.active(name, slot)` so consensus-visible behavior
changes can roll out at a coordinated slot, exactly the reference's model
(there the registry is generated from on-chain feature accounts)."""

from dataclasses import dataclass, field

# the known feature set for this chain; grows as gated behaviors land
KNOWN = (
    "strict_blockhash_age",       # enforce the 300-slot recency window
    "stake_cliff_activation",     # cliff (vs warmup-curve) stake activation
    "batch_sigverify_rlc",        # Agave flag; this verify tile is per-sig
)


@dataclass
class Features:
    activation_slot: dict[str, int | None] = field(
        default_factory=lambda: {k: 0 for k in KNOWN})

    def active(self, name: str, slot: int) -> bool:
        if name not in self.activation_slot:
            raise KeyError(f"unknown feature {name!r}")
        a = self.activation_slot[name]
        return a is not None and slot >= a

    def schedule(self, name: str, slot: int | None):
        if name not in self.activation_slot:
            raise KeyError(f"unknown feature {name!r}")
        self.activation_slot[name] = slot
