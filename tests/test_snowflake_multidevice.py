"""Snowflake answers on 8 forced host devices under both accumulation
policies, and on one device under int64, before and after appends to
CUSTOMER and ORDERS.

The device count and x64 must be set before jax imports, so the check runs
in a subprocess (the main test session keeps its single CPU device, where
``tests/test_snowflake.py`` covers one device under int32).
"""
import json
import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_ENABLE_X64"] = "1"
    sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "tests")]
    import warnings; warnings.filterwarnings("ignore")
    import numpy as np
    from repro.api import FCTRequest, FCTSession, SessionConfig
    from repro.core.star import fct_star
    from repro.launch.mesh import make_worker_mesh
    from test_snowflake import ORD, PRT, SEG, snowflake, _append_rows
    QS = [((SEG, ORD), 3), ((SEG, PRT), 4)]
    out = {}
    for n_dev, pol in ((8, "int32"), (8, "int64"), (1, "int64")):
        schema = snowflake()
        s = FCTSession(schema, mesh=make_worker_mesh(n_dev),
                       config=SessionConfig(accum_policy=pol))
        ok = True
        single = [s.query(FCTRequest(keywords=k, r_max=r)) for k, r in QS]
        batch = s.query_batch([FCTRequest(keywords=k, r_max=r)
                               for k, r in QS])
        for (kws, r), a, b in zip(QS, single, batch):
            want = fct_star(schema, kws, r)
            ok &= bool(np.array_equal(a.all_freqs, want))
            ok &= bool(np.array_equal(b.all_freqs, want))
        rng = np.random.default_rng(5)
        kws, r = QS[1]
        for rel, kw in (("CUSTOMER", SEG), ("ORDERS", ORD)):
            old = fct_star(s.schema, kws, r)
            res = s.append(rel, _append_rows(s.schema, rel, 3, rng, kw))
            want = fct_star(s.schema, kws, r)
            ok &= bool(np.array_equal(old + s.delta_freq(res, kws, r),
                                      want))
            full = s.query(FCTRequest(keywords=kws, r_max=r))
            ok &= bool(np.array_equal(full.all_freqs, want))
        out[f"{n_dev}/{pol}"] = ok
    print(json.dumps(out))
""")


def test_snowflake_answers_match_on_one_and_eight_devices_under_both_policies():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, _REPO],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"8/int32": True, "8/int64": True, "1/int64": True}
