"""Subprocess target for the SIGTERM drain drill (test_lifecycle.py).

Builds a tiny slot-scheduled endpoint, prints ``PORT=<n>`` on stdout,
then blocks in ``serve_forever()`` — which installs the SIGTERM handler.
The parent test fires requests at the port, sends SIGTERM mid-flight,
and asserts the process finishes the in-flight work, logs the drain,
and exits 0 (the crash-only lifecycle contract).
"""

from trlx_tpu import telemetry
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.serve import InferenceEngine, InferenceServer, ServeConfig

from test_serve import tiny_config_dict


def main() -> None:
    telemetry.start()
    serve = ServeConfig(
        buckets=[[2, 8, 8]], max_queue=16, request_timeout=30.0,
        slots=2, page_size=4,
        drain_timeout=20.0,
    )
    engine = InferenceEngine(TRLConfig.from_dict(tiny_config_dict()),
                             serve=serve)
    srv = InferenceServer(engine, port=0).start(warmup=True)
    print(f"PORT={srv.port}", flush=True)
    srv.serve_forever()


if __name__ == "__main__":
    main()
