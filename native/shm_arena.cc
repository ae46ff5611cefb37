// Native core for the shared-memory checkpoint arena.
//
// TPU-native analogue of the reference's pure-Python shm path
// (dlrover/python/elastic_agent/torch/ckpt_saver.py:148 _create_shared_memory
// + SharedMemoryHandler): POSIX shm_open/mmap lifecycle without Python's
// resource tracker, and crc32c-style checksums for shard integrity on
// restore.  Tensor bytes move by pwrite()/pread() on the segment's
// descriptor from Python (common/shm.py): no copy loop lives here.
//
// Exposed as a plain C ABI consumed from Python via ctypes (no pybind11 in
// this image).

#include <cerrno>
#include <cstdint>
#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

extern "C" {

// Create (or open existing) a POSIX shm segment of `size` bytes.
// Returns fd >= 0 on success, -errno on failure.
int shm_arena_create(const char* name, uint64_t size) {
  int fd = shm_open(name, O_CREAT | O_RDWR, 0600);
  if (fd < 0) return -errno;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    int e = errno;
    close(fd);
    return -e;
  }
  if ((uint64_t)st.st_size < size) {
    if (ftruncate(fd, (off_t)size) != 0) {
      int e = errno;
      close(fd);
      return -e;
    }
  }
  return fd;
}

int shm_arena_open(const char* name) {
  int fd = shm_open(name, O_RDWR, 0600);
  if (fd < 0) return -errno;
  return fd;
}

int64_t shm_arena_size(int fd) {
  struct stat st;
  if (fstat(fd, &st) != 0) return -(int64_t)errno;
  return (int64_t)st.st_size;
}

void* shm_arena_map(int fd, uint64_t size) {
  void* p = mmap(nullptr, size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (p == MAP_FAILED) return nullptr;
  return p;
}

int shm_arena_unmap(void* addr, uint64_t size) {
  return munmap(addr, size) == 0 ? 0 : -errno;
}

int shm_arena_unlink(const char* name) {
  return shm_unlink(name) == 0 ? 0 : -errno;
}

int shm_arena_close(int fd) { return close(fd) == 0 ? 0 : -errno; }

// CRC-32 (zlib polynomial, table-driven) for shard integrity checks.
static uint32_t kCrcTable[256];
static bool kCrcInit = [] {
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    kCrcTable[i] = c;
  }
  return true;
}();

uint32_t shm_crc32(const void* data, uint64_t n, uint32_t seed) {
  (void)kCrcInit;
  uint32_t c = seed ^ 0xFFFFFFFFu;
  const uint8_t* p = (const uint8_t*)data;
  for (uint64_t i = 0; i < n; ++i) c = kCrcTable[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

}  // extern "C"
