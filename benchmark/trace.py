"""Records a profiler trace of a short window and hands it to
reduce_trace.  The trace goes to a directory of its own under TMPDIR
and is deleted once reduced: a run writes little to disk."""
import glob
import os
import shutil
import tempfile

from . import reduce_trace


class Recording:
    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        kwargs = {}
        options = getattr(jax.profiler, "ProfileOptions", None)
        if options is not None:
            opts = options()
            opts.python_tracer_level = 0     # spans, not every call
            opts.host_tracer_level = 2
            kwargs["profiler_options"] = opts
        jax.profiler.start_trace(self.dir, **kwargs)
        return self

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def path(self):
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.dir}")
        return found[0]

    def reduce(self, keep_as=None, required=True):
        """The reduced trace; the files are deleted (``keep_as``
        copies the .xplane.pb somewhere first).  A rehearsal on the
        CPU has no device plane: with ``required`` off that gives
        None."""
        try:
            path = self.path()
            if keep_as:
                shutil.copy(path, keep_as)
            try:
                return reduce_trace.reduce(path)
            except reduce_trace.NoDeviceOps:
                if required:
                    raise
                return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
