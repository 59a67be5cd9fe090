#!/usr/bin/env python
"""Environment report for bug filing (ref role: tools/diagnose.py —
the reference prints platform/python/deps/hardware/network so issue
reports carry a reproducible context; same role here, for the JAX
stack this framework runs on).

Prints one JSON document; everything best-effort (a broken install
is exactly when this must still run).  It never touches a device
unless --probe is passed (a dead accelerator must not hang the
report).

    python tools/diagnose.py          # environment only, never hangs
    python tools/diagnose.py --probe  # + device enumeration (may block)
"""
import json
import os
import platform
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ver(mod):
    try:
        m = __import__(mod)
        return getattr(m, "__version__", "present")
    except Exception as exc:
        return f"MISSING ({type(exc).__name__})"


def _cmd(args):
    try:
        return subprocess.run(args, capture_output=True, text=True,
                              timeout=10).stdout.strip()[:400]
    except Exception as exc:
        return f"unavailable: {exc}"


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    probe = "--probe" in argv
    sys.path.insert(0, REPO)

    info = {
        "platform": {
            "system": platform.platform(),
            "python": sys.version.split()[0],
            "executable": sys.executable,
            "nproc": os.cpu_count(),
        },
        "versions": {m: _ver(m) for m in
                     ["numpy", "jax", "jaxlib", "flax", "optax",
                      "orbax.checkpoint", "incubator_mxnet_tpu"]},
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("JAX_", "XLA_", "MXTPU_",
                                 "PALLAS_", "TPU_", "LIBTPU"))},
        "git": {
            "head": _cmd(["git", "-C", REPO, "rev-parse", "HEAD"]),
            "status_lines": len(_cmd(
                ["git", "-C", REPO, "status", "--short"])
                .splitlines()),
        },
        "disk_free_gb": round(
            os.statvfs(REPO).f_bavail * os.statvfs(REPO).f_frsize
            / 2 ** 30, 1),
    }
    if probe:
        try:
            import jax
            info["devices"] = [
                {"platform": d.platform,
                 "kind": getattr(d, "device_kind", "")}
                for d in jax.devices()]
        except Exception as exc:
            info["devices"] = f"enumeration failed: {exc}"
    print(json.dumps(info, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
