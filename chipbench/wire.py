"""The gRPC surface the benchmark speaks: the program's generated
protobuf modules (the wire format IS the system under test's interface)
and nothing else of the program."""

from ratelimit_tpu.server import pb  # noqa: F401  (puts the protos on sys.path)

from envoy.service.ratelimit.v3 import rls_pb2  # noqa: E402,F401

METHOD = "/envoy.service.ratelimit.v3.RateLimitService/ShouldRateLimit"
