"""Test harness: force an 8-device virtual CPU platform before JAX initializes.

Multi-chip behavior (shard_map/pjit over a Mesh) is tested without TPU
hardware per the standard JAX recipe: 8 virtual CPU devices via XLA_FLAGS.
"""

import os
import sys

# Force the virtual CPU platform (must happen before jax import).
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    if os.environ.get("SDTPU_LOCKSAN") == "1":
        # Patch the threading lock factories BEFORE test modules import
        # the package, so every Class.attr lock is wrapped and named.
        from stable_diffusion_webui_distributed_tpu.runtime import locksan

        locksan.install()


flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

#: modules whose tests compile real (tiny) model pipelines — minutes of XLA
#: CPU compile time each. Everything else forms the `-m fast` tier (~2 min:
#: scheduler, config/runtime, server, samplers, xyz, cli, native, prompt).
_SLOW_MODULES = {
    "test_pipeline", "test_adapters", "test_inpaint_model",
    "test_embeddings", "test_registry", "test_esrgan", "test_goldens",
}


def pytest_generate_tests(metafunc):
    """A test that a class inherits from tests/expander_contract.py takes
    its cases from the class that binds it: ``PARAMETERS = {test name:
    [(argnames, values), ...]}``, applied as ``parametrize`` marks from the
    function outwards would be, so that each model keeps its own ids."""
    table = getattr(metafunc.cls, "PARAMETERS", {})
    for argnames, values in table.get(metafunc.definition.name, ()):
        metafunc.parametrize(argnames, values)


def pytest_sessionfinish(session, exitstatus):
    """SDTPU_LOCKSAN=1: diff the observed lock-order graph against the
    static LK005 graph; an edge the static model has no path for fails
    the run — the model must not silently diverge from reality.

    SDTPU_LOCKSAN_ORDER (default on with the sanitizer) layers the
    ordering checks on top: a Goodlock-style cycle in the union of the
    observed per-thread acquisition edges, a ``Condition.wait`` entered
    while holding an unrelated lock, or a ``lockorder a<b`` annotation
    no test exercised each fail the session — a cycle that happened not
    to interleave fatally this run is still a deadlock waiting for the
    right schedule, and an unexercised annotation is suppressing the
    static analyzer on faith."""
    if os.environ.get("SDTPU_LOCKSAN") != "1":
        return
    from stable_diffusion_webui_distributed_tpu.runtime import locksan

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures = []
    diverged = locksan.divergence(locksan.observed_edges(),
                                  locksan.static_graph(root))
    if diverged:
        failures.append(
            "observed lock orderings missing from the static graph "
            "(analysis/locks.py):\n" + "\n".join(
                f"  {a} -> {b}" for a, b in diverged))
    if os.environ.get("SDTPU_LOCKSAN_ORDER", "1") != "0":
        cycles = locksan.runtime_cycles()
        if cycles:
            failures.append(
                "runtime lock-order cycles (Goodlock union of per-thread "
                "acquisition edges):\n" + "\n".join(
                    "  " + " -> ".join(c) for c in cycles))
        waits = locksan.wait_violations()
        if waits:
            failures.append(
                "Condition.wait entered while holding unrelated lock(s):\n"
                + "\n".join(f"  held {list(held)} waiting on {cv} "
                            f"in thread {thread}"
                            for held, cv, thread in waits))
        unexercised = locksan.declared_orders(root) \
            - locksan.observed_edges()
        if unexercised:
            failures.append(
                "lockorder annotations no test exercised (an order the "
                "suite cannot demonstrate may not suppress LK005):\n"
                + "\n".join(f"  {a} < {b}"
                            for a, b in sorted(unexercised)))
    if failures:
        print("\nlocksan session gate failed:", file=sys.stderr)
        for f in failures:
            print(f, file=sys.stderr)
        session.exitstatus = 1
    else:
        print(f"\nlocksan: {len(locksan.observed_edges())} observed "
              f"edge(s), zero divergence, zero runtime cycles, zero "
              f"wait-while-holding violations", file=sys.stderr)


def pytest_collection_modifyitems(config, items):
    """Two-tier suite: `pytest -m fast` for the iteration loop, `-m slow`
    for the compiled-pipeline tests (see README "Running the tests")."""
    for item in items:
        module = item.nodeid.split("/")[-1].split(".py")[0]
        slow = module in _SLOW_MODULES \
            or item.get_closest_marker("slow") is not None
        item.add_marker(pytest.mark.slow if slow else pytest.mark.fast)


@pytest.fixture(autouse=True)
def _a_compile_cache_goes_with_the_test_that_placed_it():
    """A stage keeps its programs wherever the persistent compile cache is
    placed (serving/aot.py), so a test that places it
    (``enable_compilation_cache``: the warm-up sweep, the CLI) must not
    leave it placed for whichever test this worker runs next: that test
    would load programs an earlier run of the suite kept."""
    import jax

    was = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != was:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


#: memory maps of the process past which a test file's end lets jax's
#: compiled programs go: the kernel gives a process 65 530
#: (``vm.max_map_count``) and XLA:CPU's next compile dies at the limit
MAPS_TO_RELEASE_AT = 30_000


@pytest.fixture(scope="module", autouse=True)
def _compiled_programs_go_before_the_maps_run_out():
    """Every program jax compiles here stays for the life of the worker,
    about a dozen memory maps each; a worker that draws the files that
    compile a program a case (interpret-mode kernels above all) stood at
    63 896 of the 65 530 a process may hold, and past them XLA's next
    compile is a segmentation fault in whatever test runs then (PR 70:
    two whole runs). At a file's end, a worker over
    :data:`MAPS_TO_RELEASE_AT` clears jax's caches: the next file
    compiles what it needs again, as it would in a worker of its own."""
    yield
    try:
        with open("/proc/self/maps") as fh:
            maps = sum(1 for _ in fh)
    except OSError:
        return
    if maps > MAPS_TO_RELEASE_AT:
        import jax

        jax.clear_caches()


@pytest.fixture(scope="session")
def devices():
    import jax

    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    """dp=4 x tp=2 mesh over the virtual devices, built through the
    production mesh constructor (runtime/mesh.py)."""
    from stable_diffusion_webui_distributed_tpu.runtime.mesh import build_mesh

    return build_mesh("dp=4,tp=2")
