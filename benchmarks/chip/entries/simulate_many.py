"""The campaign runner: ``repro.scenarios.simulate_many``.

Each job is one batch of scenarios that differ only in the optical
properties of one label, the grid ``traffic["sweep"]`` spans (every
``mua`` with every ``mus``), run with the library's defaults.  The
volume is built once, at set-up, from the configuration through the
program's own volume builders; the media are traced, so one executable
serves every batch.
"""

from __future__ import annotations

import itertools


def _scenario_seed(job_seed: int, k: int) -> int:
    return (job_seed + 0x9E3779B9 * (k + 1)) % 2 ** 31


class Entry:
    def __init__(self, config: dict, traffic: dict, collect_stats: bool):
        from repro import scenarios
        from repro.core import volume as V

        self._sc = scenarios
        self.config = config
        self.photons_per_scenario = int(traffic["photons"])
        sweep = traffic["sweep"]
        self.label = int(sweep["label"])
        self.grid = [{"mua": a, "mus": s}
                     for a, s in itertools.product(sweep["mua"],
                                                   sweep["mus"])]
        self.photons = self.photons_per_scenario * len(self.grid)
        shape = tuple(config["shape"])
        media = [V.Medium(**m) for m in config["media"]]
        if config.get("sphere"):
            sp = config["sphere"]
            base = V.cube_with_sphere(shape, media[1], media[2],
                                      tuple(sp["center_mm"]),
                                      sp["radius_mm"], config["voxel_mm"])
        else:
            base = V.homogeneous_cube(shape, media[1], config["voxel_mm"])
        self.volumes = []
        for point in self.grid:
            rows = list(media)
            rows[self.label] = V.Medium(**{**config["media"][self.label],
                                           **point})
            self.volumes.append(base.with_media(rows))
        self.cfg = V.SimConfig(do_reflect=bool(config["do_reflect"]),
                               tmax_ns=float(config["tmax_ns"]),
                               n_time_gates=int(config.get("time_gates", 1)),
                               collect_stats=collect_stats)
        beam = config["beam"]
        self.source = {"type": beam["type"], "pos": beam["pos"],
                       "dir": beam["dir"]}
        self.detectors = tuple(config.get("detectors", ()))

    def _scenarios(self, seed: int, photons: int):
        return [self._sc.Scenario(vol, self.cfg, photons,
                                  seed=_scenario_seed(seed, k),
                                  source=self.source,
                                  detectors=self.detectors)
                for k, vol in enumerate(self.volumes)]

    def job(self, seed: int, photons: int | None = None):
        n = self.photons_per_scenario if photons is None else photons
        return self._sc.simulate_many(self._scenarios(seed, n))

    def answers(self, result, photons: int | None = None) -> list[dict]:
        n = self.photons_per_scenario if photons is None else photons
        return [{"result": r, "photons": n,
                 "media": {self.label: point}}
                for r, point in zip(result, self.grid)]
