"""Shared photon-step kernel contract: output spec + VMEM budget.

This module is the single statically-extractable source of truth for
the engine-parity contract that the Pallas kernel
(``photon_step.photon_step_pallas``), the pure-jnp oracle
(``ref.photon_steps_ref``), the jit wrapper (``ops.photon_steps``) and
the jnp/pallas round executors (``repro.core.simulator``) have
maintained by hand since PR 2: every mirrored implementation must
produce the same optional output groups, in the same order, gated by
the same flags.  ``reprolint`` (repro.lint, DESIGN.md
§static-analysis) parses this file with ``ast.literal_eval`` and
cross-checks each mirror against it, so the constants below must stay
plain literals — no imports, no computed values.  The runtime asserts
the same arities after every ``pallas_call`` (``output_arity``).

It also owns the kernel's VMEM budget model (DESIGN.md
§static-analysis): per grid step the kernel keeps the full volume
blocks (labels, gate-major fluence, exitance, optional Jacobian) plus
one lane block of photon state resident in VMEM.  ``check_vmem``
rejects configs that cannot fit *before* Mosaic fails to lower them;
the lint VMEM rule applies the identical formula to statically
resolvable call sites.

Keep this module dependency-free: reprolint loads it by file path
without importing jax.
"""

from __future__ import annotations

# --- output contract -------------------------------------------------------

# The photon state, packed as one PhotonState in the oracle/engine and
# unpacked into one array per field by the Pallas kernel (lane-blocked).
STATE_FIELDS = ("pos", "dir", "ivox", "label", "w", "s_left", "t", "rng",
                "alive")

# Unconditional outputs that follow the state in every mirror.
BASE_OUTPUTS = ("fluence", "exitance", "escaped", "timed")

# Optional output groups, in emission order, keyed by the flag that
# gates them.  Each mirror appends (or unpacks) exactly these arities
# under exactly these flags; "stats" is always last (DESIGN.md
# §observability).  The round executor in repro.core.simulator guards
# the stats group with its local name ``collect`` — reprolint treats
# the names in each tuple's first element as aliases of one flag.
OUTPUT_GROUPS = (
    (("n_det",), ("ppath", "det_w", "det_ppath")),
    (("record",), ("cap_det", "cap_gate")),
    (("jac_cols",), ("jac",)),
    (("stats", "collect"), ("stats",)),
)

# Positional prefix every mirrored entry point takes, in this order.
CORE_PARAMS = ("labels_flat", "media", "state", "shape", "unitinmm",
               "cfg", "n_steps")

# Optional trailing parameters every mirrored entry point accepts, in
# this relative order (the mirror-drift rule checks the subsequence).
EXT_PARAMS = ("ppath", "det_geom", "record", "jac_w", "jac_col",
              "jac_cols", "stats")

# Bytes per lane of photon state: pos/dir (3 f32 each), ivox (3 i32),
# label (i32), w/s_left/t (f32), rng (4 u32), alive (i8).
STATE_LANE_BYTES = 69


def output_arity(n_det: int = 0, record: bool = False, jac_cols: int = 0,
                 stats: bool = False, packed_state: bool = True) -> int:
    """Number of outputs a mirrored photon-step call must produce.

    ``packed_state=True`` counts the photon state as one element (the
    oracle/engine tuple); ``False`` counts one output per state field
    (the raw ``pallas_call`` output list).
    """
    n = (1 if packed_state else len(STATE_FIELDS)) + len(BASE_OUTPUTS)
    flags = {"n_det": bool(n_det), "record": bool(record),
             "jac_cols": bool(jac_cols), "stats": bool(stats)}
    for names, members in OUTPUT_GROUPS:
        if flags[names[0]]:
            n += len(members)
    return n


# --- VMEM budget -----------------------------------------------------------

# A TPU core's VMEM (16 MiB on every generation this targets), minus a
# reserve for Mosaic scratch, semaphores and the double-buffered lane
# blocks the pipeline keeps in flight.  The usable budget caps the
# gate-major fluence block at ntg <= 16 on the paper's 60^3 volume and
# the replay-Jacobian block at n_det * ntg <= 16 (DESIGN.md
# §time-resolved, §replay) — the same numbers the ROADMAP carries as
# the HBM-accumulator work item.
VMEM_BYTES = 16 * 2**20
VMEM_RESERVE_BYTES = 2 * 2**20


def estimate_vmem_bytes(nvox: int, nxy: int, ntg: int = 1,
                        block_lanes: int = 256, n_media: int = 4,
                        n_det: int = 0, record: bool = False,
                        jac_cols: int = 0, stats: bool = False) -> int:
    """Statically estimate the kernel's resident VMEM per grid step.

    Sums the full (grid-revisited) volume blocks and one lane block of
    inputs + outputs, mirroring the BlockSpecs ``photon_step_pallas``
    builds:

      labels    nvox                bytes (uint8)
      fluence   nvox * ntg * 4      bytes (gate-major f32, revisited)
      exitance  nxy * 4             bytes (revisited)
      jacobian  nvox * jac_cols * 4 bytes (revisited, replay pass B)
      media     n_media * 16        bytes
      detector  n_det * (12 + 4 * ntg + 4 * n_media) bytes
      lanes     block_lanes * (2 * state + per-lane extras)

    The estimate is deliberately simple — exact to the BlockSpec sizes,
    ignoring compiler scratch, which the reserve absorbs.
    """
    vol = nvox + nvox * ntg * 4 + nxy * 4 + nvox * jac_cols * 4
    vol += n_media * 16
    if n_det:
        # det_geom + det_w histogram + det_ppath sums (all full blocks)
        vol += n_det * (12 + 4 * ntg + 4 * n_media)
    lane = 2 * STATE_LANE_BYTES + 8          # state in+out, esc + timed
    if n_det:
        lane += 2 * 4 * n_media              # ppath in + out
    if record:
        lane += 2 * 4                        # cap_det + cap_gate
    if jac_cols:
        lane += 2 * 4                        # jac_w + jac_col inputs
    if stats:
        lane += 4 * 4                        # (n, 4) f32 telemetry block
    return vol + block_lanes * lane


def check_vmem(nvox: int, nxy: int, ntg: int = 1, block_lanes: int = 256,
               n_media: int = 4, n_det: int = 0, record: bool = False,
               jac_cols: int = 0, stats: bool = False) -> int:
    """Validate a kernel config against the VMEM budget.

    Returns the byte estimate; raises ``ValueError`` when the config
    cannot fit ``VMEM_BYTES - VMEM_RESERVE_BYTES``.  Called by
    ``photon_step_pallas`` before dispatching the *compiled* kernel
    (the interpreter has no VMEM), and by the reprolint VMEM rule for
    statically resolvable call sites — one formula, one threshold.
    """
    est = estimate_vmem_bytes(nvox, nxy, ntg, block_lanes, n_media,
                              n_det, record, jac_cols, stats)
    budget = VMEM_BYTES - VMEM_RESERVE_BYTES
    if est > budget:
        raise ValueError(
            f"photon-step kernel config needs ~{est / 2**20:.1f} MiB of "
            f"VMEM (nvox={nvox}, ntg={ntg}, jac_cols={jac_cols}, "
            f"block_lanes={block_lanes}) but only "
            f"{budget / 2**20:.1f} MiB of the {VMEM_BYTES / 2**20:.0f} "
            f"MiB core budget is usable — shrink n_time_gates / "
            f"jac_cols / block_lanes or use the jnp engine (DESIGN.md "
            f"§static-analysis; the HBM-resident accumulator is the "
            f"ROADMAP fix)")
    return est
