"""The command line, driven in process: ``repro.launch.simulate.main``.

Each job is one call of ``main(argv)`` with the argv a user would type
for the configuration, its standard output captured (the CLI's per-job
host work, its energy balance, fluence and TPSF summaries, counts as
part of the job).  Lanes, steps per round and engine are left to the
CLI's defaults.  ``traffic["devices"] == "all"`` adds ``--devices all``,
which runs ``simulate_sharded`` over a mesh of every device.
"""

from __future__ import annotations

import contextlib
import io
import json


class Entry:
    def __init__(self, config: dict, traffic: dict, collect_stats: bool):
        from repro.launch import simulate

        self._main = simulate.main
        self.config = config
        self.photons = int(traffic["photons"])
        beam = config["beam"]
        argv = ["--bench", config["program"]["bench"],
                "--size", str(config["shape"][0]),
                "--tmax-ns", repr(float(config["tmax_ns"])),
                "--source", json.dumps({"type": beam["type"],
                                        "pos": beam["pos"],
                                        "dir": beam["dir"]})]
        if config.get("time_gates", 1) != 1:
            argv += ["--time-gates", str(config["time_gates"])]
        if config.get("detectors"):
            argv += ["--detectors", json.dumps(config["detectors"])]
        if config.get("record_capacity"):
            argv += ["--save-detected", str(config["record_capacity"])]
        if traffic.get("devices") == "all":
            argv += ["--devices", "all"]
        if collect_stats:
            argv += ["--collect-stats"]
        self._argv = argv

    def job(self, seed: int, photons: int | None = None):
        n = self.photons if photons is None else photons
        with contextlib.redirect_stdout(io.StringIO()):
            return self._main(self._argv + ["--photons", str(n),
                                            "--seed", str(seed)])

    def answers(self, result, photons: int | None = None) -> list[dict]:
        """The answers one job produced: here one result of the whole
        photon budget."""
        n = self.photons if photons is None else photons
        return [{"result": result, "photons": n, "media": None}]
