"""Drive one run of the harness on the host's CPU devices, past its look
for a chip, and return the result line it printed."""
import contextlib
import io
import json
import time

import jax

from chipbench import run


def drive(root, bench, cell, seed=11, control=False, seconds=0.2,
          trace=False, chips=1):
    loaded = run.load_cell(cell, root)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.run(cell, seed, seconds, trace, loaded, bench,
                     jax.devices()[:chips], root=root, control=control,
                     t_start=time.perf_counter())
    assert rc == 0, err.getvalue()[-4000:]
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    # every number compared is printed beside its limit, last on stderr
    tail = err.getvalue().strip().splitlines()[-len(result["checks"]):]
    assert [ln.split(":")[0] for ln in tail] == [
        f"check {k}" for k in result["checks"]]
    assert list(result)[-1] == "checks"
    return result
