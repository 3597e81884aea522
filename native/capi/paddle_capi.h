/* C inference ABI — parity with the reference's paddle/capi
 * (gradient_machine.h:36-112, matrix.h, error.h): create a machine from a
 * merged model binary, feed dense float matrices, run forward, read the
 * output matrix.  Implementation: native/capi/paddle_capi.cc embeds
 * CPython and executes the model's serialized StableHLO (jax.export)
 * through paddle_tpu.capi_bridge, so serving links against ONE .so and
 * needs no model code.
 *
 * Thread-safety: entry points take the embedded interpreter's GIL for
 * marshalling; it is safe to call from N threads concurrently.  For
 * multi-threaded serving create one machine per thread with
 * paddle_gradient_machine_create_shared_param below — shared machines
 * alias ONE loaded artifact (weights are baked into the compiled
 * executable; the machine is a pure function), so there is no per-thread
 * weight copy (native/capi/examples/serve_bench.c drives 1->8 threads).
 * Per-thread compute overlap on multi-core hosts is not
 * yet measured — the standard deployment there is one process per
 * worker (the artifact file shared via the OS page cache).
 */
#ifndef PADDLE_TPU_CAPI_H
#define PADDLE_TPU_CAPI_H

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef enum {
  kPD_NO_ERROR = 0,
  kPD_NULLPTR = 1,
  kPD_OUT_OF_RANGE = 2,
  kPD_PROTOBUF_ERROR = 3, /* bad model bytes (name kept for parity) */
  kPD_NOT_SUPPORTED = 4,
  kPD_UNDEFINED_ERROR = -1,
} paddle_error;

typedef void* paddle_gradient_machine;
typedef void* paddle_matrix;

/* Initialize the runtime (embedded interpreter). argc/argv may pass
 * runtime flags, e.g. "--use_cpu" to force the CPU backend in tests. */
paddle_error paddle_init(int argc, char** argv);

/* ---- matrix ---- */
paddle_matrix paddle_matrix_create(uint64_t height, uint64_t width);
paddle_error paddle_matrix_destroy(paddle_matrix mat);
paddle_error paddle_matrix_get_shape(paddle_matrix mat, uint64_t* height,
                                     uint64_t* width);
/* Returns a mutable pointer to row r (row-major float32). */
paddle_error paddle_matrix_get_row(paddle_matrix mat, uint64_t r,
                                   float** row);

/* ---- gradient machine (inference) ---- */
paddle_error paddle_gradient_machine_create_for_inference_with_parameters(
    paddle_gradient_machine* machine, void* merged_model, uint64_t size);
paddle_error paddle_gradient_machine_load_from_path(
    paddle_gradient_machine* machine, const char* path);
/* in: array of n_in matrices (one per data layer, order = meta.json);
 * out: *n_out output matrices written to outs[0..] (caller destroys). */
paddle_error paddle_gradient_machine_forward(paddle_gradient_machine machine,
                                             paddle_matrix* in,
                                             uint64_t n_in,
                                             paddle_matrix* outs,
                                             uint64_t* n_out);
/* New machine sharing ORIGIN's loaded artifact (reference
 * gradient_machine.h:68 create_shared_param): no weight duplication —
 * the weights live once inside the compiled executable both handles
 * alias. Use one shared machine per serving thread. */
paddle_error paddle_gradient_machine_create_shared_param(
    paddle_gradient_machine* machine, paddle_gradient_machine origin);
paddle_error paddle_gradient_machine_destroy(paddle_gradient_machine machine);
/* Introspection: input count and per-input feature dim (meta.json order). */
paddle_error paddle_gradient_machine_get_num_inputs(
    paddle_gradient_machine machine, uint64_t* n);
paddle_error paddle_gradient_machine_get_input_dim(
    paddle_gradient_machine machine, uint64_t i, uint64_t* dim);

#ifdef __cplusplus
}
#endif
#endif /* PADDLE_TPU_CAPI_H */
