"""Round program: device milliseconds per round execution of the
``verify`` stage (the target's forward over the K + 1 positions) in the
backlog cell; the same reading as ``verify_device_ms.offline``."""
from bench import harness

read = harness.metric_reader("verify_device_ms.offline")
