import os

# Tests run single-device (the dry-run alone uses 512 fake devices).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# x64 stays off for tier-1 (model/kernel tests expect f32); the CI
# jax-backend job exports JAX_ENABLE_X64=1 for the parity/golden suites.
# Backend-parity tests additionally scope x64 via
# `with jax.enable_x64(True):`, so they hold under either default.
jax.config.update(
    "jax_enable_x64",
    os.environ.get("JAX_ENABLE_X64", "0").lower() in ("1", "true", "t",
                                                      "yes", "y", "on"))


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="re-baseline tests/golden/scenarios.json from the current "
             "NumPy backend instead of comparing against it (commit the "
             "diff deliberately — it redefines the regression baseline)")
