"""Pluggable photon source subsystem (DESIGN.md §sources).

Every source is a frozen dataclass with a pure, counter-seeded
``sample(photon_ids, seed) -> (pos, dir, w0, rng)`` — bit-reproducible
across single-device, sharded, chunked, and restarted runs.  The pencil
beam is the default and reproduces the historical hard-coded launch
bit-for-bit.

    from repro import sources
    res = simulate(vol, cfg, n, source=sources.Disk(pos=(30, 30, 0), radius=5))
    cfgd = sources.to_dict(src)          # JSON-friendly campaign config
    src = sources.from_dict(cfgd)        # ... and back
"""

from repro.sources.base import (
    LAUNCH_STREAM_SALT,
    PhotonSource,
    StagedSource,
    as_source,
    available_sources,
    flight_stream,
    from_dict,
    get_source_cls,
    launch_point,
    launch_stream,
    register,
    stage_source,
    staged_structure,
    to_dict,
)
from repro.sources.types import (
    Cone,
    Disk,
    GaussianBeam,
    IsotropicPoint,
    Line,
    Pencil,
    Planar,
    demo_menu,
)

__all__ = [
    "LAUNCH_STREAM_SALT",
    "PhotonSource",
    "StagedSource",
    "as_source",
    "available_sources",
    "flight_stream",
    "from_dict",
    "get_source_cls",
    "launch_point",
    "launch_stream",
    "register",
    "stage_source",
    "staged_structure",
    "to_dict",
    "Cone",
    "Disk",
    "GaussianBeam",
    "IsotropicPoint",
    "Line",
    "Pencil",
    "Planar",
    "demo_menu",
]
