"""Process lifecycle helpers: graceful shutdown hooks, stack dumps,
profiling.

Reference: weed/util/grace/ (signal_handling.go:26-65 runs registered
cleanup hooks on SIGINT/SIGTERM; pprof.go:11 SetupProfiling writes
cpu/mem profiles).  Python equivalents: SIGUSR1 dumps all thread stacks
(the pprof /debug/pprof/goroutine analogue), -cpuprofile wraps the
process in cProfile, hooks run on termination signals.
"""

from __future__ import annotations

import atexit
import cProfile
import faulthandler
import logging
import signal
import sys
import threading

log = logging.getLogger("grace")

_hooks: list = []
_installed = False
_profiler: cProfile.Profile | None = None


def on_interrupt(hook) -> None:
    """Register a cleanup hook to run on SIGINT/SIGTERM (reference:
    grace.OnInterrupt).  Registering a hook twice runs it once."""
    if hook not in _hooks:
        _hooks.append(hook)
    _install()


def _run_hooks(signum=None, frame=None) -> None:
    # drain the list so a signal-exit doesn't re-run hooks via atexit
    hooks, _hooks[:] = list(_hooks), []
    for hook in reversed(hooks):
        try:
            hook()
        except Exception:
            log.warning("shutdown hook failed", exc_info=True)
    if signum is not None:
        sys.exit(128 + signum)


def _install() -> None:
    global _installed
    if _installed or threading.current_thread() is not threading.main_thread():
        return
    _installed = True
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, _run_hooks)
        except (ValueError, OSError):
            pass
    atexit.register(_run_hooks)


def setup_stack_dumps() -> None:
    """SIGUSR1 prints every thread's stack to stderr — the 'what is this
    process doing' probe the reference gets from pprof goroutine dumps."""
    try:
        faulthandler.register(signal.SIGUSR1, all_threads=True)
    except (AttributeError, ValueError, OSError):
        pass


def setup_profiling(cpu_profile_path: str | None) -> None:
    """Start cProfile and dump to the given path at exit (reference:
    grace.SetupProfiling cpu profile)."""
    global _profiler
    if not cpu_profile_path or _profiler is not None:
        return
    _profiler = cProfile.Profile()
    _profiler.enable()

    def dump():
        global _profiler
        if _profiler is not None:
            _profiler.disable()
            _profiler.dump_stats(cpu_profile_path)
            log.info("cpu profile written to %s", cpu_profile_path)
            _profiler = None
    on_interrupt(dump)


# -- the JAX profiler: one session a process -------------------------------
#
# While a session is open the EC plane's stages (stats/pipeline.Stage)
# land on the trace's host plane as `ec.*` / `codec.*` annotations, on the
# device planes' clock.  Two things open one: `--jax-profile DIR` for the
# process's lifetime, and `GET /debug/jax_profile?seconds=N` for a window
# on a running server (stats/profile.handle_debug_jax_profile).  Both come
# through here, and SIGTERM closes whichever is open before the process
# exits, so the .xplane.pb is written.

_jax_profile_lock = threading.Lock()
_jax_profile_dir: str | None = None


def start_jax_profile(trace_dir: str) -> bool:
    """Open the session on `trace_dir`; False when one is open already.
    The Python tracer stays off: the program names its own stages, and a
    trace of two 1 GB encode calls is then half a megabyte.  Initialises
    the JAX backend, as any profiler session does."""
    global _jax_profile_dir
    import jax
    with _jax_profile_lock:
        if _jax_profile_dir is not None:
            return False
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        except RuntimeError:  # opened behind this module's back
            return False
        _jax_profile_dir = trace_dir
    log.info("jax profiler trace started -> %s", trace_dir)
    return True


def stop_jax_profile() -> str | None:
    """Close the open session -> its directory, the trace written; None
    when none is open."""
    global _jax_profile_dir
    with _jax_profile_lock:
        trace_dir, _jax_profile_dir = _jax_profile_dir, None
        if trace_dir is None:
            return None
        import jax
        try:
            jax.profiler.stop_trace()
        except RuntimeError:
            return None  # already stopped
    log.info("jax profiler trace written to %s", trace_dir)
    return trace_dir


def setup_jax_profile(trace_dir: str | None) -> None:
    """Program-level variant (the --jax-profile CLI flag): start a trace
    now, stop it at exit/interrupt."""
    if not trace_dir:
        return
    on_interrupt(stop_jax_profile)
    start_jax_profile(trace_dir)
