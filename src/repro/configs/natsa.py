"""Paper workloads: NATSA/ICCD'20 evaluates matrix profile on series of
2^16..2^19 samples with windows in the hundreds. `chip_smoke.py` and the
chip compile tests (`tests/test_tpu_compile.py`) run these sizes."""
import dataclasses


@dataclasses.dataclass(frozen=True)
class NatsaWorkload:
    name: str
    n: int
    window: int


PAPER_WORKLOADS = (
    NatsaWorkload("seismology-64k", 65536, 256),
    NatsaWorkload("epilepsy-128k", 131072, 128),
    NatsaWorkload("ecg-256k", 262144, 512),
    NatsaWorkload("power-512k", 524288, 1024),
)
