"""gesv: LU with partial pivoting, A (n x n) X = B (n x nrhs).

``call`` is the timed program's public driver call on global device
arrays; ``control`` is the control of ``correct``: the reference one
precision below the configuration, XLA's LU with partial pivoting in
the control's dtype, every product at its precision.
"""

#: the sizes n at which the benchmark's tests run ``control`` on the CPU,
#: the config's other numbers as stated, and see it fail the config's
#: limits: there it reads a ``residual`` of 3e6 to 4e6 (limit 16)
CONTROL_N = (512, 1024)


def call(st, A, B, nb, opts):
    Bm = st.Matrix.from_global(B, nb)
    X, _LU, _piv, _info = st.gesv(st.Matrix.from_global(A, nb), Bm, opts)
    return X.to_global()


def control(config, A, B):
    """The control's X for one operand pair (host float64 arrays in, a
    device array out)."""
    import jax
    import jax.numpy as jnp

    c = config["control"]
    if c["precision"] != "highest":
        raise ValueError("the gesv control runs at 'highest' only")
    dt = jnp.dtype(c["dtype"])
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.solve(jnp.asarray(A, dt), jnp.asarray(B, dt))
