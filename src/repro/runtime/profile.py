"""Named runtime profiles: one reproducible environment per serve/bench run.

Every serve report and ``BENCH_*.json`` so far recorded *ad-hoc* backend
state — whatever platform/XLA flags the process happened to inherit.  A
``RuntimeProfile`` makes that state a named, versioned artifact (in the
spirit of bayespec's ``elisa/util/config.py`` environment helpers):
platform/backend selection, an XLA flag set, host-core pinning
(``--xla_force_host_platform_device_count``), the NaN-debug toggle, x64,
and the deterministic-seed policy are resolved **once at process start**
(``resolve`` + ``apply``) and stamped into every report (``stamp``), so
CPU-interpret numbers can never be mistaken for hardware numbers and two
runs of the same profile are comparable by construction.

    from repro.runtime import profile as rt
    rt.apply(rt.resolve("ci-cpu"))      # before the first jax op
    meta["runtime"] = rt.stamp()        # in every BENCH_*.json / report

Selection order: explicit name > ``REPRO_RUNTIME_PROFILE`` env var >
``"default"``.  ``apply`` must run before JAX initializes its backend —
platform/host-device-count/XLA flags are start-of-process knobs (the
same contract as bayespec's ``set_platform``/``set_cpu_cores``).
"""

from __future__ import annotations

import dataclasses
import os
import platform as _platform
import warnings
from typing import Optional

ENV_VAR = "REPRO_RUNTIME_PROFILE"

#: where ``apply`` keeps JAX's persistent compilation cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed path inside the
#: checkout, since the path is part of every cache key
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_PROFILE_FIELDS = ("name", "platform", "host_device_count", "xla_flags",
                   "nan_debug", "x64", "seed", "deterministic")


@dataclasses.dataclass(frozen=True)
class RuntimeProfile:
    """One named runtime environment, resolved at process start.

    name               registry key, stamped into every artifact
    platform           required jax platform ("cpu"/"gpu"/"tpu"): apply
                       and stamp raise unless the first device is on
                       it; None = let jax pick (the autodetect default)
    host_device_count  pin this many host CPU devices
                       (``--xla_force_host_platform_device_count`` — the
                       sharded-serving / core-pinning knob); None = leave
    xla_flags          extra XLA_FLAGS tokens appended to the environment
    nan_debug          ``jax_debug_nans`` (fail fast on NaN scores)
    x64                ``jax_enable_x64``
    seed               the deterministic-seed policy: the base PRNG seed
                       every profiled entry point derives its keys from
    deterministic      False marks a profile whose runs are *expected* to
                       differ (e.g. time-seeded soak runs) — stamped so
                       the trend gate can refuse to compare them
    """

    name: str
    platform: Optional[str] = None
    host_device_count: Optional[int] = None
    xla_flags: tuple[str, ...] = ()
    nan_debug: bool = False
    x64: bool = False
    seed: int = 0
    deterministic: bool = True

    # -- (de)serialization round-trip --------------------------------------
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["xla_flags"] = list(self.xla_flags)
        return d

    @staticmethod
    def from_dict(d: dict) -> "RuntimeProfile":
        unknown = set(d) - set(_PROFILE_FIELDS)
        if unknown:
            raise ValueError(f"unknown RuntimeProfile fields: {sorted(unknown)}")
        d = dict(d)
        d["xla_flags"] = tuple(d.get("xla_flags") or ())
        return RuntimeProfile(**d)


#: the named registry — every entry point resolves one of these (or a
#: user-registered one) so serving/bench environments are enumerable
PROFILES: dict[str, RuntimeProfile] = {
    # honest autodetect: no forcing, deterministic seed 0
    "default": RuntimeProfile(name="default"),
    # single-process CPU dev box: pin platform so a stray GPU/TPU plugin
    # cannot silently change the numbers a debug session reproduces
    "cpu-dev": RuntimeProfile(name="cpu-dev", platform="cpu"),
    # CI profile: CPU, one pinned host device, NaN debugging off, fixed
    # seed — the environment every BENCH_*.json trend point shares
    "ci-cpu": RuntimeProfile(name="ci-cpu", platform="cpu",
                             host_device_count=1),
    # sharded-serving rehearsal on one host: 4 pinned host devices so
    # mesh plans (serve --shards) exercise the real collective paths
    "cpu-mesh4": RuntimeProfile(name="cpu-mesh4", platform="cpu",
                                host_device_count=4),
    # debugging: fail fast on NaN scores (Eq. 1 constant bugs surface as
    # NaN after division by zero-σ dims)
    "debug-nan": RuntimeProfile(name="debug-nan", platform="cpu",
                                nan_debug=True),
    # TPU serving: the first device must be a TPU, or apply() raises —
    # never a silent CPU fallback.  No TPU flags here: libtpu refuses
    # TPU-only flags passed through XLA_FLAGS and aborts the process.
    "tpu-serve": RuntimeProfile(name="tpu-serve", platform="tpu"),
}

#: the profile ``apply`` actually installed in this process (at most one)
_ACTIVE: Optional[RuntimeProfile] = None


def register(profile: RuntimeProfile) -> RuntimeProfile:
    """Add/replace a named profile (config files can extend the registry)."""
    PROFILES[profile.name] = profile
    return profile


def from_file(path) -> RuntimeProfile:
    """Load a profile from a JSON file and register it.

    The file holds one ``RuntimeProfile.to_dict()`` object (see
    ``to_file`` for the writer); unknown fields are rejected with the
    field list, so a typo'd knob cannot silently fall back to a default.
    This is the ``serve --profile-file`` path: ops can ship environment
    definitions as reviewed artifacts instead of editing code.
    """
    import json

    with open(path) as f:
        d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(
            f"profile file {path!r} must hold one JSON object "
            f"(RuntimeProfile.to_dict()), got {type(d).__name__}"
        )
    if "name" not in d:
        raise ValueError(
            f"profile file {path!r} needs a 'name' field — profiles are "
            "named artifacts stamped into every report"
        )
    return register(RuntimeProfile.from_dict(d))


def to_file(profile: RuntimeProfile, path) -> None:
    """Write ``profile`` as JSON — ``from_file``'s exact inverse."""
    import json

    with open(path, "w") as f:
        json.dump(profile.to_dict(), f, indent=2, sort_keys=True)
        f.write("\n")


def resolve(name: Optional[str] = None) -> RuntimeProfile:
    """Resolve a profile: explicit name > $REPRO_RUNTIME_PROFILE > default."""
    name = name or os.environ.get(ENV_VAR) or "default"
    try:
        return PROFILES[name]
    except KeyError:
        raise ValueError(
            f"unknown runtime profile {name!r}; registered: "
            f"{sorted(PROFILES)}"
        ) from None


def apply(profile: RuntimeProfile) -> RuntimeProfile:
    """Install ``profile`` into this process (idempotent per profile).

    Must run before the first jax operation: platform selection, host
    device count and XLA flags only take effect at backend init.  A
    second ``apply`` of the *same* profile is a no-op; a different one
    warns and is ignored (the backend is already up — restart to switch).

    A profile that names a platform raises ``RuntimeError`` unless the
    first device is on it.  The persistent compilation cache is left to
    ``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise kept at
    :data:`COMPILE_CACHE_DIR` inside the checkout.
    """
    global _ACTIVE
    import jax

    if _ACTIVE is not None:
        if profile.name != _ACTIVE.name:
            warnings.warn(
                f"runtime profile {_ACTIVE.name!r} already applied; ignoring "
                f"{profile.name!r} (profiles are process-start state)",
                RuntimeWarning, stacklevel=2,
            )
        return _ACTIVE

    tokens = list(profile.xla_flags)
    if profile.host_device_count is not None:
        tokens.append("--xla_force_host_platform_device_count="
                      f"{int(profile.host_device_count)}")
    if tokens:
        existing = os.environ.get("XLA_FLAGS", "")
        fresh = [t for t in tokens if t not in existing.split()]
        if fresh:
            os.environ["XLA_FLAGS"] = (existing + " " + " ".join(fresh)).strip()
    if profile.platform is not None:
        prev = jax.config.read("jax_platform_name")
        jax.config.update("jax_platform_name", profile.platform)
        try:
            check_platform(profile)
        except RuntimeError:
            jax.config.update("jax_platform_name", prev)
            raise
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_debug_nans", bool(profile.nan_debug))
    jax.config.update("jax_enable_x64", bool(profile.x64))
    _ACTIVE = profile
    return profile


def check_platform(profile: RuntimeProfile) -> None:
    """Raise ``RuntimeError`` unless the first device is on the platform
    ``profile`` names (no-op for autodetecting profiles)."""
    import jax

    if profile.platform is None:
        return
    try:
        got = jax.devices()[0].platform
    except RuntimeError as e:            # the named backend is absent
        raise RuntimeError(
            f"runtime profile {profile.name!r} needs platform "
            f"{profile.platform!r}, which failed to start: {e}") from None
    if got != profile.platform:
        raise RuntimeError(
            f"runtime profile {profile.name!r} needs platform "
            f"{profile.platform!r} but the first device is on {got!r}")


def active() -> RuntimeProfile:
    """The applied profile, or the resolved-but-unapplied default — so
    ``stamp`` always has a name to report."""
    return _ACTIVE if _ACTIVE is not None else resolve()


def key(profile: Optional[RuntimeProfile] = None):
    """The profile's deterministic base PRNG key (seed policy in one place)."""
    import jax

    return jax.random.PRNGKey((profile or active()).seed)


def stamp(profile: Optional[RuntimeProfile] = None) -> dict:
    """The runtime-metadata block every report/BENCH_*.json embeds.

    Resolved *facts* (backend, device kind, device count, interpret-mode
    flag) alongside the profile that asked for them — ``interpret`` is
    the "honest perf story" bit: True means every Pallas number in the
    artifact ran in CPU interpret mode and is a parity signal, not a
    hardware perf signal.
    """
    import jax

    p = profile or active()
    check_platform(p)
    backend = jax.default_backend()
    dev = jax.devices()[0]
    return {
        "profile": p.name,
        # the installed TuneTable's dispatch hash (None = fallback
        # constants) — trend.py keys comparability on it, so two runs
        # with different tunings never get compared as one trajectory
        "tune_table": _tune_table_hash(),
        "applied": _ACTIVE is not None,
        "backend": backend,
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "n_devices": len(jax.devices()),
        "interpret": backend != "tpu",
        "platform": _platform.platform(),
        "jax_version": jax.__version__,
        "seed": p.seed,
        "deterministic": p.deterministic,
        "nan_debug": p.nan_debug,
        "x64": p.x64,
        "xla_flags": list(p.xla_flags),
        "host_device_count": p.host_device_count,
    }


def _tune_table_hash() -> Optional[str]:
    """The active TuneTable's dispatch hash (lazy import — tune.table
    depends on this module for ``live_stamp``)."""
    from repro.tune import table as tunetable

    return tunetable.active_hash()


def _reset_for_tests() -> None:
    """Test hook: forget the applied profile (config flags stay as-is)."""
    global _ACTIVE
    _ACTIVE = None
