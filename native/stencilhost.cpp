// Native host-side runtime for the TPU stencil framework.
//
// The reference's host layer is native C++ (buffer management kernel.cu:184-191,
// init kernel.cu:131-146, renderer kernel.cu:115-129); this library is its
// TPU-framework counterpart, providing:
//
//   1. An async .npy writer: a background thread pool that serializes field
//      snapshots to disk (atomic tmp+rename per file) without blocking the
//      host step loop — the role the reference's host double buffer was
//      meant to play for device results (SURVEY.md C14), done properly.
//   2. Independent golden stencil engines (Game of Life per kernel.cu:10-68's
//      B3/S23 rule; 7-point FTCS per MDF_kernel.cu:20) used by the test suite
//      as a second, non-JAX implementation for differential testing.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <functional>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// minimal .npy v1.0 writer (C order, little endian)
// ---------------------------------------------------------------------------

std::string npy_header(const char* descr, const int64_t* shape, int ndim) {
  std::string dict = "{'descr': '";
  dict += descr;
  dict += "', 'fortran_order': False, 'shape': (";
  for (int i = 0; i < ndim; ++i) {
    dict += std::to_string(shape[i]);
    if (ndim == 1 || i + 1 < ndim) dict += ", ";
  }
  dict += "), }";
  // pad with spaces so that 10 + len(header) is a multiple of 64
  size_t unpadded = 10 + dict.size() + 1;  // +1 for trailing newline
  size_t padded = (unpadded + 63) / 64 * 64;
  dict.append(padded - unpadded, ' ');
  dict += '\n';

  std::string out;
  out += "\x93NUMPY";
  out += '\x01';
  out += '\x00';
  uint16_t hlen = static_cast<uint16_t>(dict.size());
  out += static_cast<char>(hlen & 0xff);
  out += static_cast<char>(hlen >> 8);
  out += dict;
  return out;
}

bool write_npy_file(const std::string& path, const char* descr,
                    const void* data, const int64_t* shape, int ndim,
                    int64_t itemsize) {
  int64_t n = 1;
  for (int i = 0; i < ndim; ++i) n *= shape[i];
  std::string tmp = path + ".tmp";
  FILE* f = std::fopen(tmp.c_str(), "wb");
  if (!f) return false;
  std::string hdr = npy_header(descr, shape, ndim);
  bool ok = std::fwrite(hdr.data(), 1, hdr.size(), f) == hdr.size();
  ok = ok && std::fwrite(data, static_cast<size_t>(itemsize),
                         static_cast<size_t>(n), f) ==
                 static_cast<size_t>(n);
  ok = std::fflush(f) == 0 && ok;
  ok = std::fclose(f) == 0 && ok;
  if (ok) ok = std::rename(tmp.c_str(), path.c_str()) == 0;
  if (!ok) std::remove(tmp.c_str());
  return ok;
}

// ---------------------------------------------------------------------------
// background writer pool
// ---------------------------------------------------------------------------

class WriterPool {
 public:
  explicit WriterPool(int n_threads) : stop_(false), pending_(0), errors_(0) {
    for (int i = 0; i < n_threads; ++i)
      workers_.emplace_back([this] { this->worker(); });
  }

  ~WriterPool() {
    {
      std::unique_lock<std::mutex> lk(mu_);
      stop_ = true;
    }
    cv_.notify_all();
    for (auto& t : workers_) t.join();
  }

  void submit(std::string path, std::string descr, std::vector<char> data,
              std::vector<int64_t> shape, int64_t itemsize) {
    {
      std::unique_lock<std::mutex> lk(mu_);
      ++pending_;
      jobs_.emplace_back([this, path = std::move(path),
                          descr = std::move(descr), data = std::move(data),
                          shape = std::move(shape), itemsize]() {
        if (!write_npy_file(path, descr.c_str(), data.data(), shape.data(),
                            static_cast<int>(shape.size()), itemsize))
          ++errors_;
      });
    }
    cv_.notify_one();
  }

  // Block until all submitted jobs completed; returns the count of writes
  // that failed since the previous wait_all (a failure is reported once,
  // not to every later caller in the process).
  int64_t wait_all() {
    std::unique_lock<std::mutex> lk(mu_);
    done_cv_.wait(lk, [this] { return pending_ == 0; });
    return errors_.exchange(0);
  }

  int64_t pending() {
    std::unique_lock<std::mutex> lk(mu_);
    return pending_;
  }

 private:
  void worker() {
    for (;;) {
      std::function<void()> job;
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_.wait(lk, [this] { return stop_ || !jobs_.empty(); });
        if (stop_ && jobs_.empty()) return;
        job = std::move(jobs_.front());
        jobs_.pop_front();
      }
      job();
      {
        std::unique_lock<std::mutex> lk(mu_);
        if (--pending_ == 0) done_cv_.notify_all();
      }
    }
  }

  std::mutex mu_;
  std::condition_variable cv_, done_cv_;
  std::deque<std::function<void()>> jobs_;
  std::vector<std::thread> workers_;
  bool stop_;
  int64_t pending_;
  std::atomic<int64_t> errors_;
};

WriterPool* pool() {
  static WriterPool p(2);
  return &p;
}

}  // namespace

extern "C" {

// Queue an async .npy write; the data is copied before returning, so the
// caller's buffer may be reused immediately.
int stencilhost_async_write_npy(const char* path, const char* descr,
                                const void* data, const int64_t* shape,
                                int ndim, int64_t itemsize) {
  int64_t n = 1;
  for (int i = 0; i < ndim; ++i) n *= shape[i];
  std::vector<char> copy(static_cast<size_t>(n * itemsize));
  std::memcpy(copy.data(), data, copy.size());
  pool()->submit(path, descr, std::move(copy),
                 std::vector<int64_t>(shape, shape + ndim), itemsize);
  return 0;
}

// Wait for all queued writes; returns the cumulative error count.
int64_t stencilhost_wait_all(void) { return pool()->wait_all(); }

int64_t stencilhost_pending(void) { return pool()->pending(); }

// Synchronous write (same format), for the fallback path and tests.
int stencilhost_write_npy(const char* path, const char* descr,
                          const void* data, const int64_t* shape, int ndim,
                          int64_t itemsize) {
  return write_npy_file(path, descr, data, shape, ndim, itemsize) ? 0 : 1;
}

// ---------------------------------------------------------------------------
// golden stencil engines (independent of JAX, for differential tests)
// ---------------------------------------------------------------------------

// One B3/S23 Game-of-Life step on an h x w int32 grid; the 1-cell frame is
// treated as fixed (never rewritten), matching the framework's guard-frame
// semantics (and kernel.cu:66's rule).
void stencilhost_life_step(const int32_t* in, int32_t* out, int64_t h,
                           int64_t w) {
  std::memcpy(out, in, sizeof(int32_t) * static_cast<size_t>(h * w));
  for (int64_t y = 1; y + 1 < h; ++y) {
    for (int64_t x = 1; x + 1 < w; ++x) {
      int n = 0;
      for (int dy = -1; dy <= 1; ++dy)
        for (int dx = -1; dx <= 1; ++dx)
          if (dy || dx) n += in[(y + dy) * w + (x + dx)];
      int32_t alive = in[y * w + x];
      out[y * w + x] = (n == 3 || (n == 2 && alive == 1)) ? 1 : 0;
    }
  }
}

// One 7-point FTCS diffusion step on a d x h x w float32 grid, frame fixed.
void stencilhost_heat3d_step(const float* in, float* out, int64_t d, int64_t h,
                             int64_t w, float alpha) {
  std::memcpy(out, in, sizeof(float) * static_cast<size_t>(d * h * w));
  for (int64_t z = 1; z + 1 < d; ++z) {
    for (int64_t y = 1; y + 1 < h; ++y) {
      for (int64_t x = 1; x + 1 < w; ++x) {
        int64_t i = (z * h + y) * w + x;
        float u = in[i];
        float lap = in[i - 1] + in[i + 1] + in[i - w] + in[i + w] +
                    in[i - h * w] + in[i + h * w] - 6.0f * u;
        out[i] = u + alpha * lap;
      }
    }
  }
}

// One 5-point FTCS diffusion step on an h x w float32 grid, frame fixed
// (the reference MDF workload, MDF_kernel.cu:20's formula class).
void stencilhost_heat2d_step(const float* in, float* out, int64_t h, int64_t w,
                             float alpha) {
  std::memcpy(out, in, sizeof(float) * static_cast<size_t>(h * w));
  for (int64_t y = 1; y + 1 < h; ++y) {
    for (int64_t x = 1; x + 1 < w; ++x) {
      int64_t i = y * w + x;
      float u = in[i];
      float lap = in[i - 1] + in[i + 1] + in[i - w] + in[i + w] - 4.0f * u;
      out[i] = u + alpha * lap;
    }
  }
}

// One first-order upwind advection step (2D), frame fixed.  cy/cx are the
// signed Courant numbers for grid axes 0/1.
void stencilhost_advect2d_step(const float* in, float* out, int64_t h,
                               int64_t w, float cy, float cx) {
  std::memcpy(out, in, sizeof(float) * static_cast<size_t>(h * w));
  for (int64_t y = 1; y + 1 < h; ++y) {
    for (int64_t x = 1; x + 1 < w; ++x) {
      int64_t i = y * w + x;
      float u = in[i];
      float acc = u;
      if (cy > 0)
        acc -= cy * (u - in[i - w]);
      else if (cy < 0)
        acc -= cy * (in[i + w] - u);
      if (cx > 0)
        acc -= cx * (u - in[i - 1]);
      else if (cx < 0)
        acc -= cx * (in[i + 1] - u);
      out[i] = acc;
    }
  }
}

// One leapfrog FDTD wave step (2D): u_new = 2u - u_prev + c2dt2*Lap(u),
// frame keeps the old u (Dirichlet by induction — ops/wave.py); the caller
// carries the old u as the next u_prev, exactly like the scan carry.
void stencilhost_wave2d_step(const float* u, const float* uprev, float* out,
                             int64_t h, int64_t w, float c2dt2) {
  std::memcpy(out, u, sizeof(float) * static_cast<size_t>(h * w));
  for (int64_t y = 1; y + 1 < h; ++y) {
    for (int64_t x = 1; x + 1 < w; ++x) {
      int64_t i = y * w + x;
      float lap = u[i - 1] + u[i + 1] + u[i - w] + u[i + w] - 4.0f * u[i];
      out[i] = 2.0f * u[i] - uprev[i] + c2dt2 * lap;
    }
  }
}

// One Gray-Scott reaction-diffusion step (2D, both fields halo'd):
// u' = u + Du*Lap(u) - u v^2 + F (1-u); v' = v + Dv*Lap(v) + u v^2 -
// (F+kappa) v (ops/reaction.py), frames fixed.
void stencilhost_grayscott2d_step(const float* u, const float* v,
                                  float* out_u, float* out_v, int64_t h,
                                  int64_t w, float du, float dv, float f,
                                  float kappa) {
  std::memcpy(out_u, u, sizeof(float) * static_cast<size_t>(h * w));
  std::memcpy(out_v, v, sizeof(float) * static_cast<size_t>(h * w));
  for (int64_t y = 1; y + 1 < h; ++y) {
    for (int64_t x = 1; x + 1 < w; ++x) {
      int64_t i = y * w + x;
      float lap_u = u[i - 1] + u[i + 1] + u[i - w] + u[i + w] - 4.0f * u[i];
      float lap_v = v[i - 1] + v[i + 1] + v[i - w] + v[i + w] - 4.0f * v[i];
      float uvv = u[i] * v[i] * v[i];
      out_u[i] = u[i] + du * lap_u - uvv + f * (1.0f - u[i]);
      out_v[i] = v[i] + dv * lap_v + uvv - (f + kappa) * v[i];
    }
  }
}

// One 27-point high-order diffusion step (3D), frame fixed.  Weights by
// neighbor class (face 14/30, edge 3/30, corner 1/30, center -128/30 —
// ops/heat.py::heat3d27's discrete operator).
void stencilhost_heat3d27_step(const float* in, float* out, int64_t d,
                               int64_t h, int64_t w, float alpha) {
  const float wface = 14.0f / 30.0f, wedge = 3.0f / 30.0f,
              wcorner = 1.0f / 30.0f, wcenter = -128.0f / 30.0f;
  std::memcpy(out, in, sizeof(float) * static_cast<size_t>(d * h * w));
  for (int64_t z = 1; z + 1 < d; ++z) {
    for (int64_t y = 1; y + 1 < h; ++y) {
      for (int64_t x = 1; x + 1 < w; ++x) {
        int64_t i = (z * h + y) * w + x;
        float acc = wcenter * in[i];
        for (int dz = -1; dz <= 1; ++dz) {
          for (int dy = -1; dy <= 1; ++dy) {
            for (int dx = -1; dx <= 1; ++dx) {
              int nz = (dz != 0) + (dy != 0) + (dx != 0);
              if (nz == 0) continue;
              float wgt = nz == 1 ? wface : (nz == 2 ? wedge : wcorner);
              acc += wgt * in[i + (dz * h + dy) * w + dx];
            }
          }
        }
        out[i] = in[i] + alpha * acc;
      }
    }
  }
}

// One red-black SOR step (2D Laplace): red half-sweep (even coordinate
// parity) then black, the black sweep reading fresh red values; frame fixed.
void stencilhost_sor2d_step(const float* in, float* out, int64_t h, int64_t w,
                            float omega) {
  std::memcpy(out, in, sizeof(float) * static_cast<size_t>(h * w));
  for (int color = 0; color < 2; ++color) {
    for (int64_t y = 1; y + 1 < h; ++y) {
      for (int64_t x = 1; x + 1 < w; ++x) {
        if (((y + x) & 1) != color) continue;
        int64_t i = y * w + x;
        float nsum = out[i - 1] + out[i + 1] + out[i - w] + out[i + w];
        out[i] = (1.0f - omega) * out[i] + omega * 0.25f * nsum;
      }
    }
  }
}

}  // extern "C"
