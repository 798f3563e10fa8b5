"""ex15: phase tracing to a Chrome trace (reference: --trace, Trace.hh).

Driver phases land on the span ring; open the export in Perfetto
(https://ui.perfetto.dev) or chrome://tracing.  Under
``jax.profiler.trace`` the same phases also appear on the profiler's
host plane, beside the device ops."""
import json
import os
import tempfile

from _common import np
import slate_tpu as st
from slate_tpu.aux import spans

spans.on()
rng = np.random.default_rng(12)
n = 64
A0 = rng.standard_normal((n, n)); S = A0 @ A0.T + n * np.eye(n)
B0 = rng.standard_normal((n, 2))
with spans.span("example"):
    st.posv(st.HermitianMatrix.from_global(S, 16, uplo=st.Uplo.Lower),
            st.Matrix.from_global(B0, 16))
path = spans.export_chrome(os.path.join(tempfile.gettempdir(),
                                        "slate_tpu_ex15_trace.json"))
names = {e["name"] for e in json.load(open(path))["traceEvents"]}
assert {"example", "posv", "potrf", "potrs"} <= names, names
print(f"ex15 trace ok: {path}")
