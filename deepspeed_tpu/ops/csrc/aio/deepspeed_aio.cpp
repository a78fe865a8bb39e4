// Async file I/O engine for tensor swapping (DeepNVMe analog).
//
// TPU-native counterpart of the reference's csrc/aio/py_lib
// (deepspeed_py_aio_handle.cpp / deepspeed_aio_thread.cpp): a pool of worker
// threads servicing pread/pwrite requests against NVMe-backed files, used by
// the ZeRO-Offload/Infinity swap layer. The reference uses libaio; this uses
// a portable thread pool issuing positional I/O (optionally O_DIRECT), which
// saturates NVMe queues just as well for the large sequential blocks the
// swapper issues, and avoids a hard libaio dependency.
//
// C ABI (ctypes-friendly): all functions exported with ds_aio_ prefix.

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <fcntl.h>
#include <mutex>
#include <string>
#include <thread>
#include <unistd.h>
#include <vector>

namespace {

struct Request {
    int64_t id;
    bool write;
    std::string path;
    void* buf;
    int64_t nbytes;
    int64_t offset;
};

struct AioHandle {
    std::vector<std::thread> workers;
    std::deque<Request> queue;
    std::mutex mu;
    std::condition_variable cv;
    std::condition_variable done_cv;
    std::atomic<int64_t> submitted{0};
    std::atomic<int64_t> completed{0};
    std::atomic<int64_t> errors{0};
    int block_size;
    bool use_direct;
    bool stop = false;

    AioHandle(int num_threads, int block_size_, bool use_direct_)
        : block_size(block_size_), use_direct(use_direct_) {
        for (int i = 0; i < num_threads; ++i) {
            workers.emplace_back([this] { this->worker_loop(); });
        }
    }

    ~AioHandle() {
        {
            std::lock_guard<std::mutex> lk(mu);
            stop = true;
        }
        cv.notify_all();
        for (auto& t : workers) t.join();
    }

    void worker_loop() {
        for (;;) {
            Request req;
            {
                std::unique_lock<std::mutex> lk(mu);
                cv.wait(lk, [this] { return stop || !queue.empty(); });
                if (stop && queue.empty()) return;
                req = queue.front();
                queue.pop_front();
            }
            if (do_io(req) != 0) errors.fetch_add(1);
            {
                // under wait_all()'s mutex: a waiter that has read the
                // counters but not yet blocked must not miss this wake-up
                std::lock_guard<std::mutex> lk(mu);
                completed.fetch_add(1);
            }
            done_cv.notify_all();
        }
    }

    int do_io(const Request& req) {
        int flags = req.write ? (O_WRONLY | O_CREAT) : O_RDONLY;
        bool direct = false;
#ifdef O_DIRECT
        // unaligned offsets cannot use O_DIRECT at all — open buffered
        if (use_direct && req.offset % 4096 == 0) { flags |= O_DIRECT; direct = true; }
#endif
        int fd = ::open(req.path.c_str(), flags, 0644);
        if (fd < 0 && direct) {  // filesystem may not support O_DIRECT
            fd = ::open(req.path.c_str(), req.write ? (O_WRONLY | O_CREAT) : O_RDONLY, 0644);
            direct = false;
        }
        if (fd < 0) return -1;
        int rc = direct ? do_io_direct(fd, req) : do_io_buffered(fd, req);
        ::close(fd);
        return rc;
    }

    int do_io_buffered(int fd, const Request& req) {
        int64_t remaining = req.nbytes;
        char* p = static_cast<char*>(req.buf);
        int64_t off = req.offset;
        // chunk into block_size pieces so queues interleave across workers
        while (remaining > 0) {
            int64_t n = remaining < block_size ? remaining : block_size;
            ssize_t r = req.write ? ::pwrite(fd, p, n, off) : ::pread(fd, p, n, off);
            if (r < 0) return -1;
            if (r == 0) break;  // EOF on read
            p += r;
            off += r;
            remaining -= r;
        }
        return remaining == 0 ? 0 : (req.write ? -1 : 0);
    }

    // O_DIRECT path: user buffers are arbitrary numpy memory, so stage
    // through a page-aligned bounce buffer (the pinned-buffer-manager role
    // of the reference's deepspeed_pin_tensor.cpp). Only reached for
    // sector-aligned offsets (do_io opens unaligned requests buffered); a
    // ragged tail is completed with an aligned full-sector transfer for
    // writes (file extended, then truncated back).
    int do_io_direct(int fd, const Request& req) {
        constexpr int64_t kAlign = 4096;
        void* bounce = nullptr;
        int64_t buf_len = block_size < kAlign ? kAlign : block_size;
        if (posix_memalign(&bounce, kAlign, buf_len) != 0) return -1;
        char* user = static_cast<char*>(req.buf);
        int64_t off = req.offset;
        int64_t remaining = req.nbytes;
        int rc = 0;
        while (remaining > 0 && rc == 0) {
            int64_t n = remaining < buf_len ? remaining : buf_len;
            int64_t n_aligned = (n + kAlign - 1) / kAlign * kAlign;
            if (req.write) {
                memcpy(bounce, user, n);
                if (n_aligned > n) memset(static_cast<char*>(bounce) + n, 0, n_aligned - n);
                ssize_t r = ::pwrite(fd, bounce, n_aligned, off);
                if (r != n_aligned) { rc = -1; break; }
            } else {
                ssize_t r = ::pread(fd, bounce, n_aligned, off);
                if (r < n) { rc = -1; break; }  // short read of live range
                memcpy(user, bounce, n);
            }
            user += n;
            off += n;
            remaining -= n;
        }
        free(bounce);
        if (rc == 0 && req.write && (req.nbytes % kAlign) != 0) {
            // trim the zero padding the last aligned sector appended
            if (::ftruncate(fd, req.offset + req.nbytes) != 0) rc = -1;
        }
        return rc;
    }

    int64_t submit(bool write, const char* path, void* buf, int64_t nbytes, int64_t offset) {
        int64_t id = submitted.fetch_add(1) + 1;
        {
            std::lock_guard<std::mutex> lk(mu);
            queue.push_back(Request{id, write, path, buf, nbytes, offset});
        }
        cv.notify_one();
        return id;
    }

    void wait_all() {
        std::unique_lock<std::mutex> lk(mu);
        done_cv.wait(lk, [this] {
            return completed.load() >= submitted.load();
        });
    }
};

}  // namespace

extern "C" {

void* ds_aio_handle_new(int num_threads, int block_size, int use_direct) {
    if (num_threads < 1) num_threads = 1;
    if (block_size < 4096) block_size = 1 << 20;
    return new AioHandle(num_threads, block_size, use_direct != 0);
}

void ds_aio_handle_free(void* h) {
    delete static_cast<AioHandle*>(h);
}

int64_t ds_aio_pread(void* h, const char* path, void* buf, int64_t nbytes, int64_t offset) {
    return static_cast<AioHandle*>(h)->submit(false, path, buf, nbytes, offset);
}

int64_t ds_aio_pwrite(void* h, const char* path, void* buf, int64_t nbytes, int64_t offset) {
    return static_cast<AioHandle*>(h)->submit(true, path, buf, nbytes, offset);
}

void ds_aio_wait(void* h) {
    static_cast<AioHandle*>(h)->wait_all();
}

int64_t ds_aio_error_count(void* h) {
    return static_cast<AioHandle*>(h)->errors.load();
}

int64_t ds_aio_inflight(void* h) {
    auto* handle = static_cast<AioHandle*>(h);
    return handle->submitted.load() - handle->completed.load();
}

}  // extern "C"
