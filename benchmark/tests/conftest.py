"""CPU-only tests of the benchmark's harness at tiny sizes.  Nothing here
describes a TPU topology or touches a device while a module is imported."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

# tiny CPU programs have no business in the persistent cache
jax.config.update("jax_enable_compilation_cache", False)

#: gpt2 family at a size a test run can hold
TINY_GPT2 = {"n_layer": 2, "n_embd": 64, "n_head": 2, "n_inner": 128,
             "n_positions": 64, "n_ctx": 64, "vocab_size": 200,
             "assumed": {"padded_vocab": 256}}
TINY_TRAIN = {"seq_len": 64, "batch_per_chip": 2, "pool_batches": 4,
              "readback_every": 2, "trace_steps": 4}
TINY_SERVE = {"rate_per_s": 8.0,
              "prompt_len": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
              "output_len": {"median": 6, "sigma": 0.6, "min": 2, "max": 16},
              "max_total": 64,
              "engine": {"n_slots": 4, "max_total": 64, "prefill_bucket": 16,
                         "queue_capacity": 16},
              "warm_prompts": [10, 20, 40], "check_requests": 4,
              "trace_seconds": 0.5, "min_tail_samples": 0}
TINY_RESNET = {"num_filters": 8, "image_size": 32, "num_classes": 10}
TINY_IMAGES = {"batch_per_chip": 4, "records": 64, "readback_every": 2,
               "trace_steps": 4}
