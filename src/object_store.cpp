// The one-file object format's native writer and reader.
//
// Same on-disk format as the Python code in
// ray_tpu/_private/object_store.py (spill/restore and lease-less writes):
//
//   [8B magic "RTPUOBJ1"][8B metadata_len][8B data_len][metadata][data]
//
// sealed atomically via rename, so Python readers/writers and this code
// interoperate on the same directory.  Exposed as a C ABI for ctypes (no
// pybind11 in this environment).  The node's store itself (capacity,
// pinning, eviction, slabs) is object_store.LocalObjectStore.
//
// Build: make -C src   ->  src/librtpu_store.so

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <string>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/file.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr char kMagic[8] = {'R', 'T', 'P', 'U', 'O', 'B', 'J', '1'};
constexpr uint64_t kHeader = 24;

std::string ObjPath(const std::string& dir, const std::string& oid_hex) {
  return dir + "/" + oid_hex + ".obj";
}

// One mapped, sealed object handed out to a reader. The fd stays open
// holding a SHARED flock for the mapping's lifetime, as the Python reader
// (object_store._read_object_file) holds one.
struct MappedObject {
  void* base = nullptr;
  uint64_t size = 0;
  int fd = -1;
};

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// stateless object IO (any process)
// ---------------------------------------------------------------------------

// Create + seal an object from N buffers. Returns total file size on
// success, 0 if the object already exists, -1 on error.
long rtpu_write_object(const char* store_dir, const char* oid_hex,
                       const uint8_t* metadata, uint64_t meta_len,
                       const uint8_t* const* bufs, const uint64_t* buf_lens,
                       uint64_t nbufs) {
  const std::string final_path = ObjPath(store_dir, oid_hex);
  struct stat st;
  if (::stat(final_path.c_str(), &st) == 0) return 0;  // immutable: no-op

  uint64_t data_len = 0;
  for (uint64_t i = 0; i < nbufs; ++i) data_len += buf_lens[i];
  const uint64_t total = kHeader + meta_len + data_len;

  const std::string tmp =
      final_path + ".building." + std::to_string(::getpid());

  int fd = ::open(tmp.c_str(), O_CREAT | O_TRUNC | O_WRONLY, 0644);
  if (fd < 0) return -1;
  // write() instead of ftruncate+mmap+memcpy: filling FRESH tmpfs pages
  // through a mapping pays a page fault + kernel zeroing per page
  // (~1.3 GB/s measured on this host); full-page write() skips the
  // zeroing and the faults (~3 GB/s).
  auto write_all = [fd](const uint8_t* p, uint64_t n) -> bool {
    while (n > 0) {
      ssize_t w = ::write(fd, p, n);
      if (w < 0 && errno == EINTR) continue;  // CPython signals lack
      // SA_RESTART in extension code; a SIGCHLD mid-copy is not an error
      if (w <= 0) return false;
      p += w;
      n -= static_cast<uint64_t>(w);
    }
    return true;
  };
  uint8_t header[kHeader];
  std::memcpy(header, kMagic, 8);
  std::memcpy(header + 8, &meta_len, 8);
  std::memcpy(header + 16, &data_len, 8);
  bool ok = write_all(header, kHeader) && write_all(metadata, meta_len);
  for (uint64_t i = 0; ok && i < nbufs; ++i) {
    ok = write_all(bufs[i], buf_lens[i]);
  }
  ::close(fd);
  if (!ok) {
    ::unlink(tmp.c_str());
    return -1;
  }
  if (::rename(tmp.c_str(), final_path.c_str()) != 0) {
    ::unlink(tmp.c_str());
    return -1;
  }
  return static_cast<long>(total);
}

// Map a sealed object read-only. On success returns an opaque handle and
// fills the out-pointers; returns nullptr if absent or corrupt.
void* rtpu_open_object(const char* store_dir, const char* oid_hex,
                       const uint8_t** meta_ptr, uint64_t* meta_len,
                       const uint8_t** data_ptr, uint64_t* data_len) {
  const std::string path = ObjPath(store_dir, oid_hex);
  int fd = ::open(path.c_str(), O_RDONLY);
  if (fd < 0) return nullptr;
  struct stat st;
  // SHARED lock for the mapping's lifetime; the inode recheck closes the
  // open->lock race against a concurrent unlink + rewrite of the path.
  struct stat pst;
  if (::flock(fd, LOCK_SH) != 0 ||
      ::stat(path.c_str(), &pst) != 0 ||
      ::fstat(fd, &st) != 0 || st.st_ino != pst.st_ino ||
      st.st_size < (off_t)kHeader) {
    ::close(fd);
    return nullptr;
  }
  void* map = ::mmap(nullptr, st.st_size, PROT_READ, MAP_SHARED, fd, 0);
  if (map == MAP_FAILED) {
    ::close(fd);
    return nullptr;
  }
  const uint8_t* p = static_cast<const uint8_t*>(map);
  if (std::memcmp(p, kMagic, 8) != 0) {
    ::munmap(map, st.st_size);
    return nullptr;
  }
  uint64_t mlen, dlen;
  std::memcpy(&mlen, p + 8, 8);
  std::memcpy(&dlen, p + 16, 8);
  if (kHeader + mlen + dlen > static_cast<uint64_t>(st.st_size)) {
    ::munmap(map, st.st_size);
    return nullptr;
  }
  *meta_ptr = p + kHeader;
  *meta_len = mlen;
  *data_ptr = p + kHeader + mlen;
  *data_len = dlen;
  auto* handle =
      new MappedObject{map, static_cast<uint64_t>(st.st_size), fd};
  return handle;
}

void rtpu_release_object(void* handle) {
  auto* h = static_cast<MappedObject*>(handle);
  if (h == nullptr) return;
  ::munmap(h->base, h->size);
  if (h->fd >= 0) ::close(h->fd);  // drops the reader's shared flock
  delete h;
}

int rtpu_object_exists(const char* store_dir, const char* oid_hex) {
  struct stat st;
  return ::stat(ObjPath(store_dir, oid_hex).c_str(), &st) == 0 ? 1 : 0;
}

}  // extern "C"
