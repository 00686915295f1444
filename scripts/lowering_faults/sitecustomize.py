"""Count the page faults the main thread takes inside each of JAX's
lowerings, without adding a frame under them.

    PYTHONPATH=scripts/lowering_faults BATON_LOWERING_FAULTS=1 \\
        JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 fedbench/run.py --workload resnet18_c128_mesh4 --rehearse-cpu \\
        2>&1 | grep LOWERING_FAULTS

CPython 3.12 keeps a thread's interpreter frames in 16 KiB chunks
(``pystate.c::push_chunk``): a call made from a frame that ends at a
chunk's edge maps a new chunk and unmaps it on return, one page fault a
call. JAX lowers a jaxpr by a recursion some 80 frames deep whose loops
call a handful of small functions an equation, so a loop that falls on
an edge pays tens of thousands of them: 0.2 s on this sandbox's kernel,
some 190 us each on the sealed machine that holds the chips (PERF.md
section 6, PR 37: 3,400 faults and 1.3 s against 17,800 and 4.1 s for
the same program). Which loop falls on an edge depends on the size of
every frame above it, ``FedSim.run_round``'s among them
(``engine.RUN_ROUND_FRAME_WORDS``), and on how the process was started:
a wrapper script, or a patch that adds a frame, measures another
program. So this replaces ``jax._src.dispatch.log_elapsed_time``, a
generator context manager whose frame is not on the data stack while its
body runs, through an import hook, and is switched on by an environment
variable because ``python3 fedbench/run.py`` has to stay the command.
Nothing here runs in the program; it is a tool of measurement.
"""
import os
import sys

if os.environ.get("BATON_LOWERING_FAULTS"):
    import contextlib
    import importlib.abc
    import importlib.machinery
    import resource
    import time

    def _patch(dispatch):
        orig = dispatch.log_elapsed_time

        @contextlib.contextmanager
        def log_elapsed_time(fmt, fun_name, event=None, **kw):
            before = resource.getrusage(resource.RUSAGE_THREAD)
            t0 = time.perf_counter()
            with orig(fmt, fun_name, event, **kw):
                yield
            wall = time.perf_counter() - t0
            if "MLIR" in fmt and wall > 0.1:
                after = resource.getrusage(resource.RUSAGE_THREAD)
                print(f"LOWERING_FAULTS {fun_name} wall {wall:.2f} s, "
                      f"faults {after.ru_minflt - before.ru_minflt}, system "
                      f"{after.ru_stime - before.ru_stime:.3f} s",
                      file=sys.stderr, flush=True)

        dispatch.log_elapsed_time = log_elapsed_time

    class _Finder(importlib.abc.MetaPathFinder):
        def find_spec(self, name, path, target=None):
            if name != "jax._src.dispatch":
                return None
            sys.meta_path.remove(self)
            spec = importlib.machinery.PathFinder.find_spec(name, path)
            exec_module = spec.loader.exec_module

            def _exec(module):
                exec_module(module)
                _patch(module)

            spec.loader.exec_module = _exec
            return spec

    sys.meta_path.insert(0, _Finder())
